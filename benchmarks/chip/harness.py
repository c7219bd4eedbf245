"""Shared pieces of one benchmark run: the cell's files, the device check,
the seeded weights, tuning, host spans and the profiler.

Nothing here names a cell, configuration, traffic mix or metric: those
are found by the names ``BENCHMARK.json`` gives them, as files under
``configs/``, ``workloads/``, ``traffic/``, ``jobs/`` and ``metrics/``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NotRunnable(Exception):
    """The run cannot produce a result here (no chip, unknown device)."""


def load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import a Python file by path (metric readers' names hold dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_files(bench: Dict, name: str) -> Dict:
    """The cell's entry and the files it names."""
    spec = find(bench["workloads"], name, "workload")
    conf_entry = find(bench["configs"], spec["config"], "config")
    return {
        "spec": spec,
        "workload": load_json(HERE / "workloads" / f"{name}.json"),
        "conf": load_json(ROOT / conf_entry["file"]),
        "traffic": load_json(HERE / "traffic" / f"{spec['traffic']}.json"),
    }


def peaks() -> Dict:
    return load_json(HERE / "peaks.json")


def device_check(chips: int) -> Tuple[Dict, Dict]:
    """(device info, peak entry); raises NotRunnable off a TPU, on a
    device kind the peaks table lacks, or with fewer chips than asked."""
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    if d.platform != "tpu":
        raise NotRunnable(f"JAX finds no TPU: {info}")
    table = peaks()
    if d.device_kind not in table:
        raise NotRunnable(f"{d.device_kind!r} is not in peaks.json")
    if len(devs) < chips:
        raise NotRunnable(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return info, table[d.device_kind]


def compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, handed to the program through the variable it reads, so
    that only a cell's first run in a checkout compiles and two
    checkouts share nothing."""
    from repro.launch.runtime import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    return enable_compile_cache()


def memory_peak() -> Optional[int]:
    import jax

    peaks_ = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


# ---------------------------------------------------------------------------
# The model under test
# ---------------------------------------------------------------------------

# configuration-file key -> the program's ModelConfig field
SHAPE_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
}


def program_config(conf: Dict):
    """The program's ModelConfig for a configuration file: the named
    repository config with the file's ``overrides``; every shape key of
    the file must agree with it."""
    from repro.configs.base import get_config

    cfg = dataclasses.replace(
        get_config(conf["repro_config"]), **conf.get("overrides", {})
    )
    for key, field in SHAPE_FIELDS.items():
        if getattr(cfg, field) != conf[key]:
            raise ValueError(
                f"{conf['name']}: {key}={conf[key]} but the program runs "
                f"{field}={getattr(cfg, field)}"
            )
    from model_ref import norm_rope

    if (cfg.norm_eps, cfg.rope_theta) != norm_rope(conf):
        raise ValueError(f"{conf['name']}: norm epsilon or rotary base differ")
    return cfg


def make_params(conf: Dict, seed: int, model) -> Dict:
    """Seeded bf16 weights made on the device, checked leaf by leaf
    against the shapes and dtypes the program's model expects."""
    import jax

    import model_ref
    from work import sizes

    params = model_ref.make_params(sizes(conf), seed)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got_s, want_s = jax.tree.structure(params), jax.tree.structure(want)
    if got_s != want_s:
        raise ValueError(f"weight tree {got_s} != program's {want_s}")
    for g, w in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if g.shape != w.shape or g.dtype != w.dtype:
            raise ValueError(f"weight {g.shape}/{g.dtype} != {w.shape}/{w.dtype}")
    return jax.block_until_ready(params)


def tune(cfg, specs, tuning: Dict, backend: str, spans) -> Tuple[Any, Dict]:
    """Tune the heaviest of ``specs`` (sorted by weight x FLOPs) whose op
    the cell names, with its fixed budget and search seed.  Returns the
    in-memory database and the search's counts."""
    from repro.search.database import Database
    from repro.search.evolutionary import SearchConfig
    from repro.search.task_scheduler import TaskScheduler
    from repro.search.tune import TuneConfig

    chosen = [s for s in specs if s.op in tuning["ops"]][: tuning["max_tasks"]]
    tasks = [s.to_tune_task(use_mxu=True) for s in chosen]
    trials = int(tuning["trials_per_task"])
    per_round = min(4, trials)
    db = Database()
    sched = TaskScheduler(
        tasks, database=db,
        config=TuneConfig(
            search=SearchConfig(
                max_trials=trials, init_random=per_round, population=8,
                measure_per_round=per_round, seed=int(tuning["seed"]),
            ),
            runner_spec="cached+local", backend=backend, warm_start=False,
            seed=int(tuning["seed"]),
        ),
    )
    with spans("tune"):
        sched.tune(total_rounds=math.ceil(trials / per_round) * len(tasks))
    sched.runner.close()
    measured = sum(len(s.measured) for s in sched.searches)
    failed = sum(
        sum(1 for v in s.measured.values() if not math.isfinite(v))
        for s in sched.searches
    )
    return db, {
        "tasks": [t.key for t in tasks],
        "tune_s": spans.total["tune"],
        "measured": measured,
        "failed": failed,
    }


def dispatch_counts(ctx) -> Dict[str, int]:
    rows = ctx.stats_by_key().values()
    return {
        "hits": sum(r["hits"] for r in rows),
        "lookups": sum(r["hits"] + r["misses"] + r["fallbacks"] for r in rows),
    }


# ---------------------------------------------------------------------------
# Host spans and the profiler
# ---------------------------------------------------------------------------


class Spans:
    """Host phases: each is a ``bench.<name>`` TraceAnnotation in the
    profiler's trace and a running total on the host clock."""

    def __init__(self):
        self.total: Dict[str, float] = defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.total[name] += time.perf_counter() - t0


class Profiler:
    """A short traced window, reduced to numbers and then deleted."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._window = None

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.traced_window")
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> Dict:
        from trace_reduce import reduce_trace

        try:
            return reduce_trace(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def free_device() -> None:
    """Drop compiled programs and collect, before the reference runs."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def setup_paths() -> None:
    for p in (str(ROOT / "src"), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)


# ---------------------------------------------------------------------------
# Arithmetic the metric readers share
# ---------------------------------------------------------------------------


def p95(values) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, float), 95))


def for_job(obs: Dict, job: str, fn):
    """``fn(obs)`` where the run is of ``job``, else None (not reported)."""
    return fn(obs) if obs.get("job") == job else None


def hit_share(obs: Dict) -> Optional[float]:
    d = obs.get("dispatch")
    if not d or not d["lookups"]:
        return None
    return 100.0 * d["hits"] / d["lookups"]


def pallas_roofline(obs: Dict) -> Optional[float]:
    from work import ideal_s

    tr = obs.get("trace")
    if not tr or not tr["pallas"]:
        return None
    took = sum(k["device_s"] for k in tr["pallas"])
    need = sum(
        k["count"] * ideal_s(k["flops"], k["bytes"], obs["peak"])
        for k in tr["pallas"]
    )
    return 100.0 * need / took if took > 0 else None


def idle_share(obs: Dict) -> Optional[float]:
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
