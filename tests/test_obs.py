"""Observability stack: tracer/spans (and their profiler annotations),
pool event emission (timeout / crash quarantine), cache + dispatch
telemetry, serving throughput, and the trace-folding report + CI gate."""

import json
import os
import time

import pytest

from repro.obs import (
    ConsoleSink,
    RingBufferSink,
    configure_tracing,
    disable_tracing,
    emit,
    span,
    spearman,
    trace_enabled,
)
from repro.obs.report import fold, load_events, render_text
from repro.obs.trace import init_from_env
from repro.search.measure import ProcessPoolRunner, structural_hash

from test_measure import _keyed_worker, mi, tiny_trace


@pytest.fixture
def sink():
    """Ring-buffer tracing scoped to one test."""
    s = RingBufferSink()
    configure_tracing(sink=s)
    yield s
    disable_tracing()


# -- rank correlation -------------------------------------------------------


class TestSpearman:
    def test_monotone_is_one(self):
        assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
        assert spearman([1, 2, 3, 4], [4, 3, 2, 1]) == pytest.approx(-1.0)

    def test_undefined_cases(self):
        assert spearman([1.0], [1.0]) is None
        assert spearman([1, 2, 3], [5, 5, 5]) is None  # constant side
        assert spearman([1, 2], [1, 2, 3]) is None  # length mismatch

    def test_ties_averaged(self):
        # with tie-averaged ranks this is a well-defined value in (0, 1)
        rho = spearman([1, 1, 2, 3], [1, 2, 3, 4])
        assert rho is not None and 0.0 < rho < 1.0


# -- tracer -----------------------------------------------------------------


class TestTracer:
    def test_disabled_is_noop(self):
        assert not trace_enabled()
        emit("nothing.listens", x=1)  # must not raise
        with span("also.nothing") as sp:
            sp.note(y=2)
        assert sp.id == 0  # shared null span

    def test_emit_and_span_nesting(self, sink):
        with span("outer", a=1) as outer:
            emit("point", k="v")
            with span("inner") as inner:
                time.sleep(0.01)
        evs = {e["ev"]: e for e in sink.events}
        assert evs["point"]["parent"] == outer.id
        assert evs["point"]["k"] == "v"
        assert evs["inner"]["parent"] == outer.id
        assert evs["inner"]["span"] == inner.id
        assert evs["inner"]["dur_s"] >= 0.01
        assert "parent" not in evs["outer"]  # root span
        assert evs["outer"]["a"] == 1
        # events appear inner-before-outer (emitted at exit)
        assert [e["ev"] for e in sink.events][-2:] == ["inner", "outer"]

    def test_span_note_and_error_capture(self, sink):
        with pytest.raises(ValueError):
            with span("boom") as sp:
                sp.note(n=3)
                raise ValueError("x")
        (e,) = sink.of_type("boom")
        assert e["n"] == 3 and e["error"] == "ValueError"

    @pytest.mark.parametrize("tracing", [True, False], ids=["on", "off"])
    def test_spans_in_profiler_trace(self, tmp_path, tracing):
        import jax
        from jax.profiler import ProfileData

        from repro.obs.trace import _NULL_SPAN

        sink = RingBufferSink()
        jax.profiler.start_trace(str(tmp_path))
        try:
            if tracing:
                configure_tracing(sink=sink)
            with span("outer") as outer:
                with span("inner") as inner:
                    time.sleep(0.002)
        finally:
            disable_tracing()
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        found = {}
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
        if not tracing:
            assert outer is _NULL_SPAN and inner is _NULL_SPAN
            assert found == {}
            return
        # nested in the trace as in the sink
        (o0, o1), (i0, i1) = found["repro.outer"], found["repro.inner"]
        assert o0 <= i0 < i1 <= o1 and i1 - i0 >= 2e6
        evs = {e["ev"]: e for e in sink.events}
        assert evs["inner"]["parent"] == evs["outer"]["span"] == outer.id

    def test_jsonl_sink_and_load_events(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        configure_tracing(path=path)
        try:
            emit("hello", x=1)
        finally:
            disable_tracing()
        events = load_events([path])
        assert [e["ev"] for e in events] == ["trace.start", "hello"]
        assert events[1]["x"] == 1

    def test_init_from_env(self, tmp_path, capsys):
        assert init_from_env({"REPRO_TRACE": ""}) is None
        assert init_from_env({"REPRO_TRACE": "0"}) is None
        try:
            path = str(tmp_path / "env.jsonl")
            assert init_from_env({"REPRO_TRACE": path}) is not None
            assert trace_enabled()
            disable_tracing()
            assert load_events([path])[0]["ev"] == "trace.start"
            assert init_from_env(
                {"REPRO_TRACE": "1", "REPRO_TRACE_PATH": path}
            ) is not None
            assert init_from_env({"REPRO_TRACE": "console"}) is not None
            emit("console.line", q=1)
            assert "console.line q=1" in capsys.readouterr().out
        finally:
            disable_tracing()

    def test_console_sink_hides_meta_fields(self, capsys):
        ConsoleSink().write(
            {"ev": "x", "ts": 1.0, "pid": 9, "span": 3, "a": 0.5}
        )
        out = capsys.readouterr().out
        assert out.strip() == "x a=0.5"

    def test_broken_sink_is_swallowed(self):
        class Bad(RingBufferSink):
            def write(self, event):
                raise RuntimeError("sink died")

        configure_tracing(sink=Bad())
        try:
            emit("still.fine")  # must not raise
        finally:
            disable_tracing()


# -- measurement events (pool timeout / crash quarantine) -------------------


class TestPoolEvents:
    def _pool(self, **kw):
        kw.setdefault("max_workers", 2)
        kw.setdefault("timeout_s", 20.0)
        kw.setdefault("grace_s", 10.0)
        kw.setdefault("worker_fn", _keyed_worker)
        return ProcessPoolRunner(**kw)

    def test_timeout_event_carries_trace_hash(self, sink):
        r = self._pool(timeout_s=0.2, grace_s=1.5, startup_grace_s=30.0)
        try:
            r.warm(wait=True)
            r.run([mi("sleep", 0)])
        finally:
            r.close()
        (ev,) = sink.of_type("measure.timeout")
        assert ev["key"] == "sleep"
        assert ev["hash"] == structural_hash("sleep", tiny_trace(0))
        assert ev["timeout_s"] == 0.2
        assert ev["backend"] == r.backend

    def test_crash_quarantine_events(self, sink):
        r = self._pool(crash_threshold=2)
        h = structural_hash("crash", tiny_trace(7))
        try:
            bad = mi("crash", 7)
            r.run([bad])
            r.run([bad])
            third = r.run([bad])  # rejected without touching the pool
            assert third[0].source == "quarantine"
        finally:
            r.close()
        crashes = sink.of_type("measure.crash")
        assert [e["crash"] for e in crashes] == [1, 2]
        assert all(e["hash"] == h for e in crashes)
        (q,) = sink.of_type("measure.crash_quarantine")
        assert q["hash"] == h and q["crashes"] == 2
        (rej,) = sink.of_type("measure.quarantine_reject")
        assert rej["hash"] == h

    def test_ok_measurement_emits_build_and_run(self, sink):
        r = self._pool()
        try:
            r.run([mi("ok:0.003", 1)])
        finally:
            r.close()
        (b,) = sink.of_type("measure.build")
        (run,) = sink.of_type("measure.run")
        assert b["ok"] and run["ok"]
        assert run["latency_s"] == 0.003
        assert run["hash"] == structural_hash("ok:0.003", tiny_trace(1))


class TestCacheEvents:
    def test_hit_and_miss_events(self, sink):
        from test_measure import CountingStubRunner

        from repro.search.measure import CachedRunner

        r = CachedRunner(CountingStubRunner())
        r.run([mi("w", 1)])
        r.run([mi("w", 1)])
        assert len(sink.of_type("cache.miss")) == 1
        assert len(sink.of_type("cache.hit")) == 1
        assert sink.of_type("cache.hit")[0]["key"] == "w"
        assert sink.of_type("cache.hit")[0]["backend"] == r.backend


# -- dispatch telemetry -----------------------------------------------------


class TestDispatchTelemetry:
    def test_reasons_stats_by_key_and_backcompat(self, sink):
        import jax.numpy as jnp

        from repro.core.workloads import get_workload
        from repro.integration.dispatch import DispatchContext
        from repro.search.database import Database

        class T:
            def __init__(self, key, func):
                self.key, self.func = key, func

        known = T("dense/k=8/m=8/n=8", get_workload("dense", m=8, n=8, k=8))
        with DispatchContext(Database(), tasks=[known]) as ctx:
            miss = ctx.dense(jnp.ones((8, 8)), jnp.ones((8, 8)))
            unknown = ctx.dense(jnp.ones((4, 4)), jnp.ones((4, 4)))
            bad = ctx.dense(jnp.ones((4, 5)), jnp.ones((7, 9)))
        assert miss is None and unknown is None and bad is None
        # legacy counters unchanged in meaning: shape fallback counts
        # neither as hit nor miss
        assert ctx.stats["hits"] == 0 and ctx.stats["misses"] == 2
        by_key = ctx.stats_by_key()
        assert by_key["dense/k=8/m=8/n=8"]["reasons"] == {"no_record": 1}
        assert by_key["dense/k=4/m=4/n=4"]["reasons"] == {"unknown_key": 1}
        assert by_key["site:dense"]["fallbacks"] == 1
        assert by_key["site:dense"]["reasons"] == {"shape_mismatch": 1}
        assert ctx.miss_reasons["dense/k=8/m=8/n=8"] == "no_record"
        evs = [e["ev"] for e in sink.events if e["ev"].startswith("dispatch.")]
        assert evs.count("dispatch.miss") == 2
        assert evs.count("dispatch.fallback") == 1

    def test_default_mode_hit_emits_event(self, sink):
        import jax.numpy as jnp

        from repro.core.workloads import get_workload
        from repro.integration.dispatch import DispatchContext

        class T:
            def __init__(self, key, func):
                self.key, self.func = key, func

        t = T("dense/k=8/m=8/n=8", get_workload("dense", m=8, n=8, k=8))
        with DispatchContext(tasks=[t], mode="default", use_mxu=False) as ctx:
            out = ctx.dense(jnp.ones((8, 8)), jnp.ones((8, 8)))
        assert out is not None
        assert ctx.stats["hits"] == 1
        (hit,) = sink.of_type("dispatch.hit")
        assert hit["key"] == "dense/k=8/m=8/n=8"
        assert hit["mode"] == "default" and hit["site"] == "dense"
        assert ctx.stats_by_key()["dense/k=8/m=8/n=8"]["hits"] == 1


# -- report folding ---------------------------------------------------------


def _synthetic_events():
    """10s tuning session: 1s build + 8s run (6s compile, 2s timing),
    2 rounds, dispatch + serve."""
    h = "abc123"
    return [
        {"ev": "trace.start", "ts": 89.0, "pid": 1},
        {"ev": "measure.build", "ts": 91.0, "dur_s": 1.0, "ok": True,
         "key": "w", "hash": h},
        {"ev": "measure.run", "ts": 95.0, "dur_s": 5.0, "ok": True,
         "key": "w", "hash": h, "latency_s": 2e-3, "compile_s": 4.0,
         "timing_s": 1.0},
        {"ev": "measure.run", "ts": 98.0, "dur_s": 3.0, "ok": True,
         "key": "w", "hash": "def456", "latency_s": 1e-3, "compile_s": 2.0,
         "timing_s": 1.0},
        {"ev": "costmodel.round", "ts": 96.0, "task": "w", "round": 1,
         "n": 4, "spearman": None, "trained": False},
        {"ev": "costmodel.round", "ts": 99.0, "task": "w", "round": 2,
         "n": 4, "spearman": 0.8, "trained": True},
        {"ev": "tune.round", "ts": 96.5, "dur_s": 6.0, "task": "w",
         "best_latency_s": 2e-3},
        {"ev": "tune.round", "ts": 99.9, "dur_s": 3.0, "task": "w",
         "best_latency_s": 1e-3},
        {"ev": "tune.session", "ts": 100.0, "dur_s": 10.0, "tasks": ["w"]},
        {"ev": "dispatch.hit", "ts": 101.0, "key": "w", "site": "dense",
         "mode": "best"},
        {"ev": "dispatch.hit", "ts": 101.1, "key": "w", "site": "dense",
         "mode": "best"},
        {"ev": "dispatch.miss", "ts": 101.2, "key": "x", "site": "rmsnorm",
         "mode": "best", "reason": "no_record"},
        {"ev": "dispatch.fallback", "ts": 101.3, "key": None,
         "site": "attention", "mode": "best", "reason": "decode_offset"},
        {"ev": "serve.prefill", "ts": 102.0, "tokens": 100, "dur_s": 2.0},
        {"ev": "serve.decode", "ts": 104.0, "tokens": 30, "dur_s": 3.0},
    ]


class TestReportFold:
    def test_time_breakdown_accounts_session(self):
        rep = fold(_synthetic_events())
        tb = rep["time_breakdown"]
        assert rep["wall_s"] == pytest.approx(10.0)
        assert tb["build_s"] == pytest.approx(1.0)
        assert tb["compile_s"] == pytest.approx(6.0)
        assert tb["timing_s"] == pytest.approx(2.0)
        assert tb["search_overhead_s"] == pytest.approx(1.0)
        assert tb["accounted_frac"] >= 0.9

    def test_cost_model_dispatch_slowest_serving(self):
        rep = fold(_synthetic_events())
        cm = rep["cost_model"]["w"]
        assert cm["mean_spearman"] == pytest.approx(0.8)
        assert [r["round"] for r in cm["rounds"]] == [1, 2]
        d = rep["dispatch"]
        assert (d["hits"], d["misses"], d["fallbacks"]) == (2, 1, 1)
        assert d["hit_rate"] == pytest.approx(2 / 3, abs=1e-4)
        assert d["by_key"]["x"]["reasons"] == {"no_record": 1}
        assert d["by_key"]["site:attention"]["fallbacks"] == 1
        assert rep["slowest"][0]["latency_us"] == pytest.approx(2000.0)
        assert rep["serving"]["prefill_tok_s"] == pytest.approx(50.0)
        assert rep["serving"]["decode_tok_s"] == pytest.approx(10.0)
        assert rep["rounds"] == 2 and rep["tasks"]["w"]["rounds"] == 2

    def test_render_text_smoke(self):
        txt = render_text(fold(_synthetic_events()))
        for section in ("time breakdown", "cost model", "dispatch coverage",
                        "serving"):
            assert section in txt

    def test_fold_without_session_uses_trace_extent(self):
        events = [e for e in _synthetic_events()
                  if e["ev"] != "tune.session"]
        rep = fold(events)
        assert rep["wall_s"] > 0
        assert rep["time_breakdown"]["accounted_frac"] >= 0.9


class TestRegressionGate:
    def _check(self):
        import importlib.util

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "check_regression",
            os.path.join(root, "benchmarks", "check_regression.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_hit_rate_floor(self, tmp_path):
        mod = self._check()
        bench = tmp_path / "bench.json"
        bench.write_text(json.dumps(
            {"models": [{"model": "m", "speedup": 1.5, "tasks": []}]}
        ))
        report = tmp_path / "report.json"
        report.write_text(json.dumps({"dispatch": {
            "hit_rate": 0.5, "hits": 5, "misses": 5}}))
        assert mod.check(
            bench, report=str(report), min_dispatch_hit_rate=0.4
        ) == 0
        assert mod.check(
            bench, report=str(report), min_dispatch_hit_rate=0.6
        ) == 1

    def test_missing_hit_rate_fails_when_required(self, tmp_path):
        mod = self._check()
        bench = tmp_path / "bench.json"
        bench.write_text(json.dumps(
            {"models": [{"model": "m", "speedup": 1.5, "tasks": []}]}
        ))
        report = tmp_path / "report.json"
        report.write_text(json.dumps({"dispatch": {"hit_rate": None}}))
        assert mod.check(
            bench, report=str(report), min_dispatch_hit_rate=0.1
        ) == 1


# -- serving throughput -----------------------------------------------------


class TestServingThroughput:
    def test_tok_s_properties_and_events(self, sink):
        import jax
        import numpy as np

        from repro.configs.base import get_config
        from repro.models.registry import build_model
        from repro.serving.engine import ServingEngine

        cfg = get_config("smollm-135m", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, max_batch=2, max_seq=32)
        assert eng.prefill_tok_s == 0.0 and eng.decode_tok_s == 0.0
        eng.submit(np.arange(4), max_new_tokens=3)
        eng.submit(np.arange(6), max_new_tokens=3)
        eng.run()
        assert eng.stats["prefill_tokens"] == 12
        assert eng.stats["decode_tokens"] == 4  # 2 reqs x 2 loop tokens
        assert eng.prefill_tok_s > 0 and eng.decode_tok_s > 0
        (p,) = sink.of_type("serve.prefill")
        (d,) = sink.of_type("serve.decode")
        assert p["tokens"] == 12 and d["tokens"] == 4
        assert d["steps"] == 2
        assert p["model"] == d["model"] == cfg.name
        assert d["dur_s"] > 0 and d["tok_s"] > 0
