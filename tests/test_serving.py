"""Continuous-batching serving: slot pool + KV arena mechanics, the
scheduler's equivalence with the sequential baseline (mixed lengths,
recycling, prefill joining a live decode batch), decode-shape task
extraction and tuned dispatch, the engine's early decode-loop stop, and
extraction-skip accounting."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.modules import SpaceGenerator, default_modules
from repro.core.validator import validate_trace
from repro.integration.dispatch import DispatchContext
from repro.integration.extract import (
    decode_attention_sites,
    extract_decode_task_specs,
    extract_decode_tasks,
)
from repro.models.registry import build_model
from repro.obs import RingBufferSink, configure_tracing, disable_tracing
from repro.obs.report import fold
from repro.search.database import Database, TuningRecord
from repro.serving import (
    ContinuousBatchingScheduler,
    KVArena,
    ServingEngine,
    SlotPool,
)

MAX_SEQ = 32
SLOTS = 2


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-135m", smoke=True)


@pytest.fixture(scope="module")
def setup(cfg):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


def _baseline(cfg, params, prompts, budgets, dispatch=None):
    """Sequential reference: one request at a time, batch=1."""
    eng = ServingEngine(
        cfg, params, max_batch=1, max_seq=MAX_SEQ, dispatch=dispatch
    )
    for p, b in zip(prompts, budgets):
        eng.submit(p, max_new_tokens=b)
    return [list(r.generated) for r in eng.run()]


class TestSlotPool:
    def test_alloc_lowest_first_and_exhaustion(self):
        pool = SlotPool(2)
        assert pool.alloc() == 0
        assert pool.alloc() == 1
        assert pool.free == 0 and pool.in_use == 2
        with pytest.raises(IndexError):
            pool.alloc()

    def test_release_recycles_and_rejects_double_free(self):
        pool = SlotPool(2)
        a = pool.alloc()
        pool.release(a)
        with pytest.raises(ValueError):
            pool.release(a)
        with pytest.raises(ValueError):
            pool.release(7)
        assert pool.alloc() == a  # recycled, lowest-first


class TestKVArena:
    def test_load_and_release_roundtrip(self, cfg, setup):
        model, _ = setup
        arena = KVArena(model, SLOTS, MAX_SEQ)
        assert arena.positions.shape == (SLOTS,)
        rc = dict(model.init_cache(1, MAX_SEQ))
        rc["k"] = jnp.ones_like(rc["k"]) * 3
        rc["pos"] = jnp.asarray(5, jnp.int32)
        arena.load_slot(1, rc)
        assert int(arena.positions[1]) == 5
        assert int(arena.positions[0]) == 0
        assert float(jnp.abs(arena.cache["k"][:, 1] - 3).max()) == 0
        assert float(jnp.abs(arena.cache["k"][:, 0]).max()) == 0  # other lane
        arena.release_slot(1)
        assert int(arena.positions[1]) == 0
        assert float(jnp.abs(arena.cache["k"][:, 1]).max()) == 0


class TestScheduler:
    def test_recycles_slots_and_matches_sequential_baseline(self, cfg, setup):
        # 6 requests through 2 slots: mixed prompt lengths and budgets,
        # greedy — token streams must match the one-at-a-time engine
        _, params = setup
        lens = [4, 8, 6, 8, 4, 6]
        budgets = [3, 5, 2, 4, 6, 1]
        prompts = _prompts(cfg, lens)
        want = _baseline(cfg, params, prompts, budgets)
        sched = ContinuousBatchingScheduler(
            cfg, params, n_slots=SLOTS, max_seq=MAX_SEQ
        )
        for p, b in zip(prompts, budgets):
            sched.submit(p, max_new_tokens=b)
        reqs = sched.run()
        assert [list(r.generated) for r in reqs] == want
        assert all(r.done for r in reqs)
        assert sched.stats["admitted"] == 6
        assert sched.stats["released"] == 6
        assert sched.stats["peak_active"] == SLOTS  # oversubscribed pool
        assert sched.pool.free == SLOTS  # every slot returned

    def test_prefill_joins_live_decode(self, cfg, setup):
        # C arrives while A is mid-decode; C must take B's freed slot and
        # decode alongside A without perturbing either stream
        _, params = setup
        prompts = _prompts(cfg, [4, 6, 5])
        budgets = [8, 2, 3]
        want = _baseline(cfg, params, prompts, budgets)
        sched = ContinuousBatchingScheduler(
            cfg, params, n_slots=2, max_seq=MAX_SEQ
        )
        a = sched.submit(prompts[0], max_new_tokens=budgets[0])
        b = sched.submit(prompts[1], max_new_tokens=budgets[1])
        while not b.done:
            sched.step()
        assert not a.done  # A still decoding when B's slot frees
        c = sched.submit(prompts[2], max_new_tokens=budgets[2])
        sched.step()  # admits C into the freed slot mid-flight
        assert c.slot is not None and len(sched.active) == 2
        sched.run()
        got = [list(r.generated) for r in (a, b, c)]
        assert got == want

    def test_prefill_only_request_releases_immediately(self, cfg, setup):
        _, params = setup
        prompts = _prompts(cfg, [5])
        want = _baseline(cfg, params, prompts, [1])
        sched = ContinuousBatchingScheduler(
            cfg, params, n_slots=SLOTS, max_seq=MAX_SEQ
        )
        r = sched.submit(prompts[0], max_new_tokens=1)
        sched.run()
        assert r.done and list(r.generated) == want[0]
        assert sched.stats["decode_steps"] == 0
        assert r.ttft_s is not None and r.latency_s is not None

    def test_rejects_overlong_prompt(self, cfg, setup):
        _, params = setup
        sched = ContinuousBatchingScheduler(
            cfg, params, n_slots=1, max_seq=8
        )
        with pytest.raises(ValueError):
            sched.submit(np.zeros(9, np.int32))


class TestDecodeDispatch:
    def test_decode_extraction_keys(self, cfg):
        specs = extract_decode_task_specs(
            cfg, batch=SLOTS, max_seq=MAX_SEQ, dispatchable_only=True
        )
        ops = {s.op for s in specs}
        assert "attention_decode" in ops and "dense" in ops
        attn = [s for s in specs if s.op == "attention_decode"]
        # key is the static decode shape: pool size + full cache length
        assert all(s.kwargs["b"] == SLOTS for s in attn)
        assert all(s.kwargs["t"] == MAX_SEQ for s in attn)
        assert all(f"/t={MAX_SEQ}" in s.key for s in attn)
        dense = [s for s in specs if s.op == "dense"]
        assert all(s.kwargs["m"] == SLOTS for s in dense)

    def test_tuned_dispatch_serves_decode_and_tokens_match(self, cfg, setup):
        # the scheduler under a db-best context must hit the decode-shape
        # attention + dense keys and emit the same greedy tokens as the
        # default-schedule (untuned) context
        _, params = setup
        tasks = extract_decode_tasks(
            cfg, batch=SLOTS, max_seq=MAX_SEQ, dispatchable_only=True
        )
        db = Database(None)
        for t in tasks:
            gen = SpaceGenerator(default_modules(use_mxu=t.use_mxu))
            for s in range(8):
                v = validate_trace(t.func, gen.generate(t.func, seed=s).trace)
                if v.ok:
                    db.put(TuningRecord(
                        t.key, v.schedule.trace.to_json(), 1e-6, time.time()
                    ))
                    break
        tuned_ctx = DispatchContext(db, tasks=tasks, mode="best")
        untuned_ctx = DispatchContext(None, tasks=tasks, mode="default")
        prompts = _prompts(cfg, [4, 6, 5])
        budgets = [4, 3, 5]
        streams = {}
        for name, ctx in [("tuned", tuned_ctx), ("untuned", untuned_ctx)]:
            sched = ContinuousBatchingScheduler(
                cfg, params, n_slots=SLOTS, max_seq=MAX_SEQ, dispatch=ctx
            )
            for p, b in zip(prompts, budgets):
                sched.submit(p, max_new_tokens=b)
            streams[name] = [list(r.generated) for r in sched.run()]
        assert streams["tuned"] == streams["untuned"]
        for ctx in (tuned_ctx, untuned_ctx):
            hit_ops = {k.split("/", 1)[0] for k in ctx.hits_by_key}
            assert "attention_decode" in hit_ops
            assert "dense" in hit_ops
            assert ctx.stats["attention_decode_tuned"] >= 1


class TestEngineEarlyStop:
    def test_no_decode_steps_when_all_budgets_are_one(self, cfg, setup):
        _, params = setup
        eng = ServingEngine(cfg, params, max_batch=2, max_seq=MAX_SEQ)
        for p in _prompts(cfg, [4, 6]):
            eng.submit(p, max_new_tokens=1)
        reqs = eng.run()
        assert eng.stats["decode_steps"] == 0
        assert all(len(r.generated) == 1 and r.done for r in reqs)

    def test_short_request_stops_appending_in_mixed_batch(self, cfg, setup):
        _, params = setup
        eng = ServingEngine(cfg, params, max_batch=2, max_seq=MAX_SEQ)
        prompts = _prompts(cfg, [4, 6])
        eng.submit(prompts[0], max_new_tokens=3)
        eng.submit(prompts[1], max_new_tokens=2)
        reqs = eng.run()
        assert eng.stats["decode_steps"] == 2  # longest budget governs
        assert [len(r.generated) for r in reqs] == [3, 2]


class TestExtractSkip:
    def _record(self, **over):
        rec = dict(
            q_shape=(2, 3, 1, 16), kvh=1, kv_seq=MAX_SEQ, causal=True,
            window=0, softcap=0.0, scale=None, q_offset=0, kind="decode",
        )
        rec.update(over)
        return rec

    def test_skip_increments_counter_with_reason(self, cfg):
        sink = RingBufferSink()
        configure_tracing(sink=sink)
        try:
            sites = decode_attention_sites(
                cfg,
                [
                    self._record(scale=0.123),  # nondefault_scale
                    self._record(window="traced"),  # traced_window
                    self._record(),  # kept
                ],
            )
        finally:
            disable_tracing()
        assert len(sites) == 1
        reasons = [e["reason"] for e in sink.of_type("extract.skip")]
        assert sorted(reasons) == ["nondefault_scale", "traced_window"]

    def test_report_folds_skip_events(self):
        events = [
            {"ev": "extract.skip", "ts": 1.0,
             "site": "attention_decode", "reason": "traced_window"},
            {"ev": "extract.skip", "ts": 1.1,
             "site": "attention_decode", "reason": "traced_window"},
            {"ev": "extract.skip", "ts": 1.2,
             "site": "attention", "reason": "cross_attention"},
        ]
        report = fold(events)
        assert report["extract_skips"] == {
            "attention_decode/traced_window": 2,
            "attention/cross_attention": 1,
        }
