"""Paged serving tier: ServeConfig coercion + legacy-kwarg shim, paged
KV arena page accounting, paged-vs-contiguous scheduler equivalence on
the jnp and Pallas-interpret decode paths, in-tick chunked prefill
token-order preservation, the release stale-state regression, the paged
flash-decode kernel, and the streaming request API."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.integration.dispatch import DispatchContext
from repro.integration.extract import extract_decode_tasks
from repro.kernels.flash_attention import (
    decode_flash_attention,
    paged_decode_flash_attention,
)
from repro.models.registry import build_model
from repro.serving import (
    ContinuousBatchingScheduler,
    PagedKVArena,
    ServeConfig,
    ServingEngine,
)
from repro.serving.config import coerce_serve_config
from repro.serving.kv import snap_page_size

MAX_SEQ = 32
SLOTS = 2
PAGE = 8
CHUNK = 4


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
    eng = ServingEngine(
        cfg, params,
        config=ServeConfig(max_slots=1, max_seq=MAX_SEQ, dispatch=dispatch),
    )
    for p, b in zip(prompts, budgets):
        eng.submit(p, max_new_tokens=b)
    return [list(r.generated) for r in eng.run()]


def _run_sched(cfg, params, prompts, budgets, sc):
    sched = ContinuousBatchingScheduler(cfg, params, config=sc)
    reqs = [
        sched.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)
    ]
    sched.run()
    return sched, [list(r.generated) for r in reqs]


class TestServeConfig:
    def test_importable_from_lazy_surface(self):
        import repro

        assert repro.ServeConfig is ServeConfig

    def test_legacy_kwargs_warn_once_and_map(self, cfg, recwarn):
        import repro.serving.config as scmod

        scmod._legacy_warned = False
        with pytest.warns(DeprecationWarning, match="deprecated"):
            sc = coerce_serve_config(
                None, {"n_slots": 3, "max_seq": 16}, "TestCaller"
            )
        assert sc.max_slots == 3 and sc.max_seq == 16
        # legacy construction selects exactly the PR 7 behavior
        assert sc.paged is False and sc.prefill_chunk == 0
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # second use must stay silent
            coerce_serve_config(None, {"n_slots": 3}, "TestCaller")

    def test_unknown_kwarg_raises_type_error(self):
        with pytest.raises(TypeError, match="unexpected keyword"):
            coerce_serve_config(None, {"max_slotz": 3}, "TestCaller")

    def test_config_plus_legacy_raises(self):
        with pytest.raises(TypeError, match="both"):
            coerce_serve_config(ServeConfig(), {"n_slots": 3}, "TestCaller")

    def test_validation(self):
        with pytest.raises(ValueError):
            ServeConfig(max_slots=0)
        with pytest.raises(ValueError):
            ServeConfig(page_size=0)
        with pytest.raises(ValueError):
            ServeConfig(prefill_chunk=-1)

    def test_resolved_forces_paged_off_for_ssm(self):
        mamba = get_config("mamba2-370m", smoke=True)
        sc = ServeConfig(paged=None, prefill_chunk=8).resolved_for(mamba)
        assert sc.paged is False and sc.prefill_chunk == 0

    def test_tick_budget_default(self):
        sc = ServeConfig(max_slots=4, prefill_chunk=8)
        assert sc.tick_budget == 12
        assert ServeConfig(token_budget=7).tick_budget == 7


class TestSnapPageSize:
    def test_divisor_snapping(self):
        assert snap_page_size(32, 16) == 16
        assert snap_page_size(32, 12) == 8  # largest divisor <= 12
        assert snap_page_size(30, 16) == 15
        assert snap_page_size(7, 16) == 7
        assert snap_page_size(32, 1) == 1


class TestPagedKVArena:
    def test_reserve_release_page_accounting(self, cfg, setup):
        model, _ = setup
        arena = PagedKVArena(model, SLOTS, MAX_SEQ, page_size=PAGE)
        total = arena.total_pages
        assert arena.free_pages == total
        need = arena.pages_needed(PAGE + 1)  # spills into a second page
        assert need == 2
        got = arena.reserve(0, PAGE + 1)
        assert got == 2 and arena.free_pages == total - 2
        # page table points at real pages, sentinel in the tail
        row = np.asarray(arena.cache["page_table"][0])
        assert (row[:2] < total).all() and (row[2:] == total).all()
        with pytest.raises(ValueError):
            arena.reserve(0, 4)  # double reservation
        arena.release_slot(0)
        assert arena.free_pages == total
        assert (np.asarray(arena.cache["page_table"][0]) == total).all()

    def test_exhaustion_gates_admission(self, cfg, setup):
        model, _ = setup
        arena = PagedKVArena(
            model, SLOTS, MAX_SEQ, page_size=PAGE, total_pages=3
        )
        assert arena.can_admit(PAGE * 2) and not arena.can_admit(PAGE * 4)
        arena.reserve(0, PAGE * 2)
        assert not arena.can_admit(PAGE * 2)  # 1 page left, needs 2
        with pytest.raises(IndexError):
            arena.reserve(1, PAGE * 2)
        arena.release_slot(0)
        assert arena.can_admit(PAGE * 2)

    def test_release_zeroes_only_owned_pages(self, cfg, setup):
        model, _ = setup
        arena = PagedKVArena(model, SLOTS, MAX_SEQ, page_size=PAGE)
        arena.reserve(0, PAGE * 2)
        arena.reserve(1, PAGE)
        # write through slot 1's page, then release slot 0: slot 1's
        # data must survive (only slot 0's pages are scrubbed)
        p1 = int(np.asarray(arena.cache["page_table"][1][0]))
        arena.cache["k"] = arena.cache["k"].at[:, p1].set(7.0)
        arena.release_slot(0)
        assert float(jnp.abs(arena.cache["k"][:, p1] - 7.0).max()) == 0
        arena.release_slot(1)
        assert float(jnp.abs(arena.cache["k"]).max()) == 0

    def test_rejects_non_attention_model(self):
        mamba = get_config("mamba2-370m", smoke=True)
        with pytest.raises(ValueError, match="pure-attention"):
            PagedKVArena(build_model(mamba), SLOTS, MAX_SEQ)


class TestReleaseStaleState:
    def test_contiguous_release_prefix_clears_written_state(self, cfg, setup):
        # regression: release used to zero the whole max_seq lane; now it
        # zeroes only the written prefix — which must still leave the
        # lane fully clean, because a request never writes past its pos
        from repro.serving.kv import KVArena

        model, _ = setup
        arena = KVArena(model, SLOTS, MAX_SEQ)
        rc = dict(model.init_cache(1, MAX_SEQ))
        used = 5
        rc["k"] = rc["k"].at[:, :, :, :used].set(3.0)
        rc["v"] = rc["v"].at[:, :, :, :used].set(3.0)
        rc["pos"] = jnp.asarray(used, jnp.int32)
        arena.load_slot(0, rc)
        arena.release_slot(0, used=used)
        assert float(jnp.abs(arena.cache["k"][:, 0]).max()) == 0
        assert float(jnp.abs(arena.cache["v"][:, 0]).max()) == 0
        assert int(arena.positions[0]) == 0

    def test_recycled_slot_streams_stay_clean(self, cfg, setup):
        # 3x oversubscription through 1 slot: any stale KV surviving a
        # release would perturb the next request's greedy stream
        _, params = setup
        prompts = _prompts(cfg, [6, 4, 8])
        budgets = [3, 4, 2]
        want = _baseline(cfg, params, prompts, budgets)
        for paged in (False, True):
            _, got = _run_sched(
                cfg, params, prompts, budgets,
                ServeConfig(
                    max_slots=1, max_seq=MAX_SEQ, paged=paged,
                    page_size=PAGE, prefill_chunk=CHUNK,
                ),
            )
            assert got == want, f"paged={paged}"


class TestPagedEquivalence:
    LENS = [4, 8, 6, 5, 7]
    BUDGETS = [3, 5, 2, 4, 3]

    def _variants(self):
        return {
            "paged_chunked": ServeConfig(
                max_slots=SLOTS, max_seq=MAX_SEQ, paged=True,
                page_size=PAGE, prefill_chunk=CHUNK,
            ),
            "paged_whole": ServeConfig(
                max_slots=SLOTS, max_seq=MAX_SEQ, paged=True,
                page_size=PAGE, prefill_chunk=0,
            ),
            "contiguous_chunked": ServeConfig(
                max_slots=SLOTS, max_seq=MAX_SEQ, paged=False,
                prefill_chunk=CHUNK,
            ),
        }

    def test_streams_match_sequential_baseline_jnp(self, cfg, setup):
        _, params = setup
        prompts = _prompts(cfg, self.LENS)
        want = _baseline(cfg, params, prompts, self.BUDGETS)
        for name, sc in self._variants().items():
            sched, got = _run_sched(cfg, params, prompts, self.BUDGETS, sc)
            assert got == want, name
            assert sched.pool.free == SLOTS, name
        # the chunked run really chunked (not silently whole-prefilling)
        assert sched.stats["prefill_chunks"] >= len(prompts)

    def test_streams_match_on_pallas_interpret(self, cfg, setup):
        # the paged decode tick reads KV through the page-table gather;
        # dispatching its attention site to the Pallas interpret backend
        # must not change greedy streams
        _, params = setup
        tasks = extract_decode_tasks(
            cfg, batch=SLOTS, max_seq=MAX_SEQ, dispatchable_only=True,
            chunk=CHUNK, paged=True, page_size=PAGE,
        )
        ctx = DispatchContext(
            None, tasks=tasks, mode="default", backend="pallas-interpret"
        )
        prompts = _prompts(cfg, [4, 6])
        budgets = [3, 2]
        want = _baseline(cfg, params, prompts, budgets)
        sched, got = _run_sched(
            cfg, params, prompts, budgets,
            ServeConfig(
                max_slots=SLOTS, max_seq=MAX_SEQ, paged=True,
                page_size=PAGE, prefill_chunk=CHUNK, dispatch=ctx,
            ),
        )
        assert got == want
        hit_ops = {k.split("/", 1)[0] for k in ctx.hits_by_key}
        assert "attention_decode" in hit_ops  # served, not fallen back

    def test_page_accounting_invariants_every_tick(self, cfg, setup):
        # step the scheduler by hand and check the page pool's books
        # after every tick: free never negative, owned+free == total,
        # no page owned twice
        _, params = setup
        prompts = _prompts(cfg, self.LENS)
        sched = ContinuousBatchingScheduler(
            cfg, params,
            config=ServeConfig(
                max_slots=SLOTS, max_seq=MAX_SEQ, paged=True,
                page_size=PAGE, prefill_chunk=CHUNK,
            ),
        )
        arena = sched.arena
        for p, b in zip(prompts, self.BUDGETS):
            sched.submit(p, max_new_tokens=b)
        while sched.pending():
            sched.step()
            owned = [p for ps in arena._owned.values() for p in ps]
            assert arena.free_pages >= 0
            assert len(owned) == len(set(owned))
            assert arena.free_pages + len(owned) == arena.total_pages
        assert arena.free_pages == arena.total_pages

    def test_chunked_prefill_preserves_token_order(self, cfg, setup):
        # a prompt longer than one chunk must hit the cache in order:
        # its positions after admission equal the prompt length, and the
        # first sampled token matches the whole-prompt prefill's
        _, params = setup
        (prompt,) = _prompts(cfg, [CHUNK * 3 + 1])
        want = _baseline(cfg, params, [prompt], [2])
        sched, got = _run_sched(
            cfg, params, [prompt], [2],
            ServeConfig(
                max_slots=1, max_seq=MAX_SEQ, paged=True,
                page_size=PAGE, prefill_chunk=CHUNK,
            ),
        )
        assert got == want
        # 13-token prompt through width-4 chunks: 4 chunk ticks
        assert sched.stats["prefill_chunks"] == 4
        assert sched.stats["prefill_tokens"] == len(prompt)


class TestPagedDecodeKernel:
    def test_matches_contiguous_decode_kernel(self):
        B, KVH, G, D, T = 2, 2, 3, 16, 32
        ps = 8
        P = T // ps
        n_pages = B * P + 2
        key = jax.random.PRNGKey(0)
        kq, kk, kv, kt = jax.random.split(key, 4)
        q = jax.random.normal(kq, (B, KVH, G, D), jnp.float32)
        k_pool = jax.random.normal(kk, (n_pages, KVH, ps, D), jnp.float32)
        v_pool = jax.random.normal(kv, (n_pages, KVH, ps, D), jnp.float32)
        # shuffled non-contiguous tables, one sentinel entry (masked off)
        perm = np.array(
            jax.random.permutation(kt, n_pages - 1)[: B * P]
        ).reshape(B, P)
        perm[1, -1] = n_pages  # sentinel: unallocated tail page
        table = jnp.asarray(perm, jnp.int32)
        lengths = jnp.asarray([T, T - ps], jnp.int32)  # B's tail unused
        pos = jnp.arange(T)[None, :]
        bias = jnp.where(pos < lengths[:, None], 0.0, -1e30)
        # reference: gather the pages into a contiguous view
        gathered_k = (
            k_pool[jnp.minimum(table, n_pages - 1)]
            .transpose(0, 2, 1, 3, 4).reshape(B, KVH, T, D)
        )
        gathered_v = (
            v_pool[jnp.minimum(table, n_pages - 1)]
            .transpose(0, 2, 1, 3, 4).reshape(B, KVH, T, D)
        )
        want = decode_flash_attention(
            q, gathered_k, gathered_v, bias, interpret=True
        )
        got = paged_decode_flash_attention(
            q, k_pool, v_pool, table, bias, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-6, rtol=2e-6
        )


class TestStreamingRequest:
    def test_tokens_streams_while_scheduler_runs(self, cfg, setup):
        _, params = setup
        (prompt,) = _prompts(cfg, [5])
        sched = ContinuousBatchingScheduler(
            cfg, params,
            config=ServeConfig(
                max_slots=1, max_seq=MAX_SEQ, paged=True,
                page_size=PAGE, prefill_chunk=CHUNK,
            ),
        )
        r = sched.submit(prompt, max_new_tokens=4)
        streamed = list(r.tokens())
        assert r.done and streamed == list(r.generated)
        assert len(streamed) == 4

    def test_unattached_request_raises(self):
        from repro.serving.request import Request

        r = Request(0, np.zeros(3, np.int32), 2, 0.0)
        with pytest.raises(RuntimeError):
            next(r.tokens())

    def test_engine_and_scheduler_share_request_type(self):
        from repro.serving.engine import Request as EngineRequest
        from repro.serving.request import Request
        from repro.serving.scheduler import ServeRequest

        assert EngineRequest is Request and ServeRequest is Request
