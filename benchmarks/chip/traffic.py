"""The one traffic generator: reads a mix's parameters, draws from the seed.

A mix is a data file ``traffic/<name>.json``; its ``kind`` picks one of
the two shapes of load this generator makes:

* ``packed``: fixed batches of ``batch`` x ``seq`` token ids, made on the
  device from the seed and the step number, so every step's rows differ;
* ``open_loop``: requests due at Poisson arrival times, with lognormal
  prompt and output lengths (``median``, ``sigma``, clipped to ``min`` /
  ``max``) and a ``greedy_share`` of requests decoded greedily (the rest
  at ``temperature``).  Every seed gets the same set of gaps and lengths,
  the stratified quantiles of their distributions, in another order: the
  seed changes which request comes when, not how much work there is.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> Dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


# ---------------------------------------------------------------------------
# packed batches
# ---------------------------------------------------------------------------


def packed_batch_fn(mix: Dict, vocab: int, seed: int):
    """``batch(step)`` -> int32 (batch, seq) token ids on the device."""
    import jax
    import jax.numpy as jnp

    from harness import prng_key

    key = jax.random.fold_in(prng_key(seed), 1)

    @partial(jax.jit, static_argnums=1)
    def make(step, shape):
        return jax.random.randint(
            jax.random.fold_in(key, step), shape, 0, vocab, jnp.int32
        )

    shape = (int(mix["batch"]), int(mix["seq"]))
    return lambda step: make(np.uint32(step), shape)


# ---------------------------------------------------------------------------
# open-loop requests
# ---------------------------------------------------------------------------


@dataclass
class Arrival:
    due_s: float
    prompt: np.ndarray
    max_new: int
    temperature: float


def lognormal_lengths(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a clipped lognormal, ascending."""
    nd = NormalDist()
    u = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in u])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def open_loop(
    mix: Dict, rate: float, seconds: float, vocab: int, seed: int
) -> List[Arrival]:
    """Requests due in ``[0, seconds)`` at ``rate`` per second."""
    n = max(1, int(math.floor(rate * seconds)))
    r = rng(seed, 2)
    u = (np.arange(n) + 0.5) / n
    gaps = r.permutation(-np.log1p(-u) / rate)
    # the first request is due at 0; the gaps are scaled by one factor,
    # the same for every order, so that all n fall inside the window
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    prompts = r.permutation(lognormal_lengths(mix["prompt"], n))
    outputs = r.permutation(lognormal_lengths(mix["output"], n))
    greedy = np.zeros(n, bool)
    greedy[: int(round(mix["greedy_share"] * n))] = True
    greedy = r.permutation(greedy)
    temp = float(mix["temperature"])
    toks = rng(seed, 3)
    return [
        Arrival(
            float(due[i]),
            toks.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            int(outputs[i]),
            0.0 if greedy[i] else temp,
        )
        for i in range(n)
    ]
