"""Batched serving engine: continuous prefill + decode over a KV cache.

Request lifecycle: submit → (batched) prefill → decode loop → done.  The
engine keeps one fixed-shape batch slot per concurrent request so every
decode step is a single compiled ``decode_step`` call (static shapes; the
dry-run's ``decode_*`` cells lower exactly this function).  Greedy or
temperature sampling.

Construction takes a :class:`~repro.serving.config.ServeConfig`
(``ServingEngine(cfg, params, config=ServeConfig(max_slots=8))``); the
old loose kwargs (``max_batch`` / ``max_seq`` / ``seed`` / ``dispatch``)
still work through a warn-once deprecation shim.  The engine always runs
the contiguous whole-batch layout — the paged arena and in-tick chunked
prefill live in :class:`~repro.serving.scheduler
.ContinuousBatchingScheduler`.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models.registry import build_model
from ..obs import emit, trace_enabled
from .config import ServeConfig, coerce_serve_config
from .request import Request


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        config: Optional[ServeConfig] = None,
        **legacy,
    ):
        self.config = coerce_serve_config(config, legacy, "ServingEngine")
        sc = self.config
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.max_batch = sc.max_slots
        self.max_seq = sc.max_seq
        self.rng = np.random.default_rng(sc.seed)
        # tuned-kernel dispatch: the context must be active while jit
        # *traces* prefill/decode (shapes are static then); per-engine
        # lambdas keep the jit caches per-context.
        self.dispatch = sc.dispatch
        self._prefill = jax.jit(
            lambda p, c, toks: self.model.prefill(p, c, tokens=toks)
        )
        self._decode = jax.jit(
            lambda p, c, toks: self.model.decode_step(p, c, toks)
        )
        self._requests: List[Request] = []
        self.stats: Dict[str, float] = {
            "prefill_tokens": 0, "decode_steps": 0, "decode_tokens": 0,
            "prefill_s": 0.0, "decode_s": 0.0,
        }

    @property
    def prefill_tok_s(self) -> float:
        """Prompt tokens ingested per second of prefill wall-clock."""
        s = self.stats["prefill_s"]
        return self.stats["prefill_tokens"] / s if s > 0 else 0.0

    @property
    def decode_tok_s(self) -> float:
        """Tokens generated per second of decode-loop wall-clock."""
        s = self.stats["decode_s"]
        return self.stats["decode_tokens"] / s if s > 0 else 0.0

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               temperature: Optional[float] = None) -> Request:
        if temperature is None:
            temperature = self.config.temperature
        r = Request(len(self._requests), np.asarray(prompt, np.int32),
                    max_new_tokens, temperature)
        r._pump = self.run
        r.mark_submitted()
        self._requests.append(r)
        return r

    def _sample(self, logits: np.ndarray, temperature: float) -> int:
        if temperature <= 0:
            return int(np.argmax(logits))
        p = np.exp((logits - logits.max()) / temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    def run(self) -> List[Request]:
        """Serve all submitted requests in fixed-size batches.

        Batches whose requests already finished are skipped, so run()
        is re-entrant: the streaming ``Request.tokens()`` pump and late
        ``submit()`` + ``run()`` rounds only pay for unfinished work."""
        for i in range(0, len(self._requests), self.max_batch):
            batch = self._requests[i: i + self.max_batch]
            if all(r.done for r in batch):
                continue
            self._run_batch(batch)
        return self._requests

    def _dctx(self):
        from ..integration.dispatch import maybe_dispatch

        return maybe_dispatch(self.dispatch)

    def _run_batch(self, reqs: List[Request]) -> None:
        B = len(reqs)
        S = max(len(r.prompt) for r in reqs)
        prompts = np.zeros((B, S), np.int32)
        for j, r in enumerate(reqs):
            prompts[j, S - len(r.prompt):] = r.prompt  # left-pad
        cache = self.model.init_cache(B, max_seq=self.max_seq)
        t0 = time.perf_counter()
        with self._dctx():
            logits, cache = self._prefill(self.params, cache, jnp.asarray(prompts))
        logits = np.asarray(logits.astype(jnp.float32))
        dt = time.perf_counter() - t0
        self.stats["prefill_s"] += dt
        self.stats["prefill_tokens"] += B * S
        if trace_enabled():
            emit(
                "serve.prefill",
                model=self.cfg.name,
                batch=B,
                tokens=B * S,
                dur_s=round(dt, 6),
                tok_s=round(B * S / dt, 3) if dt > 0 else None,
            )
        nxt = np.array(
            [self._sample(logits[j, 0], r.temperature) for j, r in enumerate(reqs)],
            np.int32,
        )
        now = time.perf_counter()
        for j, r in enumerate(reqs):
            r.generated.append(int(nxt[j]))
            r.first_token_s = now
        for j, r in enumerate(reqs):
            r.done = len(r.generated) >= r.max_new_tokens
        max_new = max(r.max_new_tokens for r in reqs)
        new_tokens = 0
        steps_run = 0
        t0 = time.perf_counter()
        for step in range(max_new - 1):
            if all(r.done for r in reqs):
                break  # every request in flight finished: stop decoding
            with self._dctx():
                logits, cache = self._decode(
                    self.params, cache, jnp.asarray(nxt[:, None])
                )
            self.stats["decode_steps"] += 1
            steps_run += 1
            la = np.asarray(logits[:, 0].astype(jnp.float32))
            nxt = np.array(
                [self._sample(la[j], r.temperature) for j, r in enumerate(reqs)],
                np.int32,
            )
            for j, r in enumerate(reqs):
                if len(r.generated) < r.max_new_tokens:
                    r.generated.append(int(nxt[j]))
                    new_tokens += 1
                if len(r.generated) >= r.max_new_tokens:
                    r.done = True
        dt = time.perf_counter() - t0
        self.stats["decode_s"] += dt
        self.stats["decode_tokens"] += new_tokens
        if trace_enabled():
            emit(
                "serve.decode",
                model=self.cfg.name,
                batch=B,
                steps=steps_run,
                tokens=new_tokens,
                dur_s=round(dt, 6),
                tok_s=round(new_tokens / dt, 3) if dt > 0 else None,
            )
        now = time.perf_counter()
        for r in reqs:
            r.done = True
            if r.finish_s is None:
                r.finish_s = now
