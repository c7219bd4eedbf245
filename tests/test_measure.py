"""Measurement subsystem: hashing, cache dedup, pool timeout/quarantine,
registry, batched evolutionary integration, database round-trip."""

import os
import time

import numpy as np
import pytest

from repro.core.trace import Instruction, Trace, new_expr_rv
from repro.search.database import Database, TuningRecord
from repro.search.measure import (
    CachedRunner,
    LegacyRunnerAdapter,
    MeasureInput,
    MeasureResult,
    ProcessPoolRunner,
    Runner,
    as_runner,
    create_runner,
    structural_hash,
)
from repro.search.measure.local import LocalRunner as ProtocolLocalRunner


def tiny_trace(decision: int) -> Trace:
    return Trace(
        [
            Instruction(
                "sample_categorical",
                [],
                {"candidates": [0, 1, 2, 3]},
                [new_expr_rv(decision)],
                decision,
            )
        ]
    )


def mi(key: str, decision: int = 0) -> MeasureInput:
    # func=None is fine for stub/pool-stub runners: only the trace and the
    # workload key participate in hashing and in the stub workers below
    return MeasureInput(key, None, tiny_trace(decision))


# -- stub pool workers (module-level: spawn pickles them by reference) -----


def _keyed_worker(payload):
    """Latency encoded in the workload key: 'ok:<latency>'; 'sleep' hangs;
    'crash' kills the worker process."""
    key = payload["workload_key"]
    if key.startswith("sleep"):
        time.sleep(60)
    if key.startswith("crash"):
        os._exit(13)
    return {
        "latency_s": float(key.split(":")[1]),
        "error": "",
        "build_time_s": 0.0,
        "run_time_s": 0.0,
    }


# -- structural hashing ----------------------------------------------------


class TestStructuralHash:
    def test_same_trace_same_hash(self):
        assert structural_hash("k", tiny_trace(1)) == structural_hash(
            "k", tiny_trace(1)
        )

    def test_decision_changes_hash(self):
        assert structural_hash("k", tiny_trace(1)) != structural_hash(
            "k", tiny_trace(2)
        )

    def test_workload_key_changes_hash(self):
        assert structural_hash("a", tiny_trace(1)) != structural_hash(
            "b", tiny_trace(1)
        )

    def test_numpy_decisions_normalized(self):
        t = tiny_trace(1)
        t.insts[0].decision = np.int64(1)
        assert structural_hash("k", t) == structural_hash("k", tiny_trace(1))


# -- cache semantics -------------------------------------------------------


class CountingStubRunner(Runner):
    name = "stub"

    def __init__(self, latency=1e-3, fail_keys=()):
        self.calls = 0
        self.seen = []
        self.latency = latency
        self.fail_keys = set(fail_keys)

    def run(self, inputs):
        self.calls += 1
        self.seen.extend(inputs)
        return [
            MeasureResult(float("inf"), "boom")
            if m.workload_key in self.fail_keys
            else MeasureResult(self.latency)
            for m in inputs
        ]


class TestCachedRunner:
    def test_repeat_is_cache_hit(self):
        inner = CountingStubRunner()
        r = CachedRunner(inner)
        first = r.run([mi("w", 1)])
        second = r.run([mi("w", 1)])
        assert first[0].ok and second[0].ok
        assert second[0].source == "cache"
        assert len(inner.seen) == 1  # inner measured exactly once
        assert r.stats()["cache_hits"] == 1
        assert r.stats()["cache_misses"] == 1

    def test_intra_batch_duplicates_deduped(self):
        inner = CountingStubRunner()
        r = CachedRunner(inner)
        out = r.run([mi("w", 1), mi("w", 2), mi("w", 1)])
        assert len(out) == 3
        assert len(inner.seen) == 2  # the duplicate never reached inner
        assert out[2].source == "cache"
        assert r.hits == 1 and r.misses == 2

    def test_failures_are_cached_too(self):
        inner = CountingStubRunner(fail_keys={"w"})
        r = CachedRunner(inner)
        a = r.run([mi("w", 1)])
        b = r.run([mi("w", 1)])
        assert not a[0].ok and not b[0].ok
        assert b[0].source == "cache"
        assert len(inner.seen) == 1

    def test_name_composes(self):
        assert CachedRunner(CountingStubRunner()).name == "cached+stub"


# -- process pool ----------------------------------------------------------


class TestProcessPool:
    def _pool(self, **kw):
        kw.setdefault("max_workers", 2)
        kw.setdefault("timeout_s", 20.0)
        kw.setdefault("grace_s", 10.0)
        kw.setdefault("worker_fn", _keyed_worker)
        return ProcessPoolRunner(**kw)

    def test_results_in_input_order(self):
        r = self._pool()
        try:
            lats = [0.004, 0.001, 0.003, 0.002]
            out = r.run([mi(f"ok:{l}", i) for i, l in enumerate(lats)])
            assert [x.latency_s for x in out] == lats
            assert all(x.ok and x.source == "measured" for x in out)
        finally:
            r.close()

    def test_timeout_returns_inf_and_recovers(self):
        r = self._pool(timeout_s=0.2, grace_s=1.5, startup_grace_s=30.0)
        try:
            r.warm(wait=True)  # charge the tight budget to candidates only
            out = r.run([mi("sleep", 0), mi("ok:0.001", 1)])
            hung = out[0]
            assert not hung.ok and "timeout" in hung.error
            assert hung.source == "timeout"
            # the pool was torn down; a fresh batch must still work
            ok = r.run([mi("ok:0.002", 2)])
            assert ok[0].latency_s == 0.002
            assert r.stats()["timeouts"] >= 1
        finally:
            r.close()

    def test_crash_quarantine(self):
        r = self._pool(crash_threshold=2)
        try:
            bad = mi("crash", 7)
            first = r.run([bad])
            assert not first[0].ok and "crash" in first[0].error
            second = r.run([bad])
            assert not second[0].ok
            assert r.stats()["quarantined_traces"] == 1
            third = r.run([bad])  # now rejected without touching the pool
            assert third[0].source == "quarantine"
            # an unrelated trace is unaffected
            ok = r.run([mi("ok:0.001", 1)])
            assert ok[0].ok
        finally:
            r.close()

    def test_crash_in_mixed_batch_attributed_by_isolated_retry(self):
        r = self._pool(crash_threshold=2)
        try:
            out = r.run([mi("ok:0.001", 1), mi("crash", 7), mi("ok:0.002", 2)])
            assert out[0].latency_s == 0.001
            assert out[2].latency_s == 0.002
            assert not out[1].ok
            # only the crashing trace accumulated a crash count
            assert list(r.crash_counts.values()) == [1]
        finally:
            r.close()


# -- registry --------------------------------------------------------------


class TestRegistry:
    def test_compose_cached_local(self):
        r = create_runner("cached+local")
        assert isinstance(r, CachedRunner)
        assert isinstance(r.inner, ProtocolLocalRunner)
        assert r.name == "cached+local"

    def test_unknown_spec_raises(self):
        with pytest.raises(KeyError):
            create_runner("warp-drive")
        with pytest.raises(KeyError):
            create_runner("bogus+local")

    def test_as_runner_passthrough_and_adapter(self):
        from repro.search.runner import LocalRunner as LegacyLocal

        stub = CountingStubRunner()
        assert as_runner(stub) is stub
        adapted = as_runner(LegacyLocal())
        assert isinstance(adapted, LegacyRunnerAdapter)
        assert isinstance(as_runner(None), ProtocolLocalRunner)
        assert isinstance(as_runner("cached+pool"), CachedRunner)

    def test_as_runner_rejects_garbage(self):
        with pytest.raises(TypeError):
            as_runner(42)


# -- batched evolutionary integration (stub runner: no jax measurement) ----


class HashLatencyStubRunner(Runner):
    """Deterministic fake latency from the trace hash; some hashes fail."""

    name = "stub"

    def __init__(self, fail_every: int = 5):
        self.fail_every = fail_every
        self.batches = []

    def run(self, inputs):
        self.batches.append(len(inputs))
        out = []
        for m in inputs:
            h = int(structural_hash(m.workload_key, m.trace), 16)
            if h % self.fail_every == 0:
                out.append(MeasureResult(float("inf"), "stub failure"))
            else:
                out.append(MeasureResult(1e-4 + (h % 997) * 1e-7))
        return out


class TestEvolutionaryBatched:
    def test_search_uses_batches_and_records_provenance(self, tmp_path):
        from repro.core.modules import SpaceGenerator, default_modules
        from repro.core.workloads import get_workload
        from repro.search.evolutionary import EvolutionarySearch, SearchConfig

        func = get_workload("gmm", n=32, m=32, k=32)
        space = SpaceGenerator(default_modules(False))
        runner = HashLatencyStubRunner(fail_every=4)
        db = Database(str(tmp_path / "db.json"))
        search = EvolutionarySearch(
            func,
            space,
            runner=runner,
            database=db,
            workload_key="gmm/test",
            config=SearchConfig(
                max_trials=12, population=8, init_random=6,
                generations=1, measure_per_round=4,
            ),
        ).tune()
        # measurements went through the runner as per-round batches
        assert len(runner.batches) >= 2
        assert max(runner.batches) > 1
        assert len(search.measured) <= 12
        assert np.isfinite(search.best_latency)
        # failures were counted per round and errors retained
        assert len(search.failure_counts) == len(runner.batches)
        assert search.total_failures == len(search.errors)
        # the database best carries build/run provenance in meta
        rec = db.best("gmm/test")
        assert rec is not None
        assert rec.meta["runner"] == "stub"
        assert rec.meta["source"] == "measured"
        assert "failures_so_far" in rec.meta and "trials_so_far" in rec.meta


# -- trace JSON round-trip (regression) ------------------------------------


class TestTraceJsonRoundTrip:
    def test_requeried_loop_outputs_survive_roundtrip(self):
        """Regression: to_json derived output ids from len(rv_ids); an
        instruction re-outputting an RV equal to an earlier output (e.g.
        get_loops after split) then aliased two outputs to one id, and the
        deserialized trace replayed onto the wrong loops."""
        from repro.core.modules import SpaceGenerator, default_modules
        from repro.core.validator import validate_trace
        from repro.core.workloads import get_workload

        func = get_workload("fused_dense", m=32, n=64, k=32)
        space = SpaceGenerator(default_modules(True))
        checked = 0
        for seed in range(8):
            t = space.generate(func, seed=seed).trace
            v_mem = validate_trace(func, t)
            v_json = validate_trace(func, Trace.from_json(t.to_json()))
            assert v_mem.ok == v_json.ok, getattr(v_json, "reason", "")
            checked += v_mem.ok
        assert checked > 0  # at least one valid schedule exercised replay


# -- database round-trip ---------------------------------------------------


class TestDatabaseRoundTrip:
    def test_persistence_topk_and_meta(self, tmp_path):
        path = str(tmp_path / "db.json")
        db = Database(path, top_k=3)
        for i in range(8):
            db.put(
                TuningRecord(
                    "wl",
                    tiny_trace(i % 8).to_json(),
                    latency_s=1e-3 * (8 - i),
                    timestamp=float(i),
                    meta={"runner": "pool", "build_time_s": 0.1 * i},
                )
            )
        db2 = Database(path, top_k=3)
        rows = db2.top("wl", 10)
        assert len(rows) == 3  # pruned to top_k
        lats = [r.latency_s for r in rows]
        assert lats == sorted(lats)
        assert db2.best("wl").latency_s == pytest.approx(1e-3)
        assert rows[0].meta["runner"] == "pool"

    def test_identical_trace_deduped(self, tmp_path):
        db = Database(str(tmp_path / "db.json"), top_k=5)
        t = tiny_trace(1).to_json()
        db.put(TuningRecord("wl", t, 2e-3, meta={"runner": "pool"}))
        db.put(TuningRecord("wl", t, 1e-3, meta={"runner": "pool"}))
        rows = db.top("wl", 10)
        assert len(rows) == 1
        assert rows[0].latency_s == pytest.approx(1e-3)
        assert rows[0].meta["times_measured"] == 2

    def test_put_batch_single_save(self, tmp_path):
        path = str(tmp_path / "db.json")
        db = Database(path, top_k=2)
        db.put_batch(
            [TuningRecord("wl", tiny_trace(i).to_json(), 1e-3 * (i + 1)) for i in range(4)]
        )
        assert len(Database(path).top("wl", 10)) == 2


# -- compile / timing split --------------------------------------------------


class TestCompileTimingSplit:
    def test_local_runner_times_first_call_apart(self):
        from repro.core.modules import SpaceGenerator, default_modules
        from repro.core.validator import first_valid_schedule
        from repro.core.workloads import get_workload
        from repro.obs import RingBufferSink, configure_tracing, disable_tracing

        func = get_workload("dense", m=16, n=16, k=16)
        sch = first_valid_schedule(func, SpaceGenerator(default_modules(False)))
        sink = RingBufferSink()
        configure_tracing(sink=sink)
        try:
            (res,) = ProtocolLocalRunner(backend="jnp").run(
                [MeasureInput("dense/k=16/m=16/n=16", func, sch.trace, sch)]
            )
        finally:
            disable_tracing()
        assert res.ok and res.compile_s > 0 and res.timing_s > 0
        (run,) = sink.of_type("measure.run")
        assert run["compile_s"] == res.compile_s
        assert run["timing_s"] == res.timing_s
        assert res.compile_s + res.timing_s <= run["dur_s"] + 1e-6
        # the first call ran inside its own span, before the event
        (comp,) = sink.of_type("measure.compile")
        assert comp["dur_s"] <= res.compile_s + 1e-6
