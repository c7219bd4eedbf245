"""The ``afmoe`` reference module's step counts against hand counts, the
grouped-kernel roofline reader on hand-made trace rows, and the prefill
job driven on the CPU through a sparse-expert configuration."""

import pytest
from benchlib import CHIP, peak, small_conf

import harness

harness.setup_paths()
import run as bench_run  # noqa: E402
import work  # noqa: E402

afmoe = harness.reference({"name": "afmoe", "reference": "afmoe"})
grouped_roofline = harness.load_module(CHIP / "metrics" / "prefill.grouped_roofline.py")

# six layers: two dense, then one period of three sliding and one full
SMALL = {
    "num_hidden_layers": 6, "num_dense_layers": 2, "hidden_size": 8,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
    "intermediate_size": 16, "moe_intermediate_size": 4, "num_experts": 4,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "vocab_size": 32,
    "sliding_window": 3, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "route_scale": 2.0,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 2,
}


def test_window_pairs_follow_causal_pairs():
    # 5 queries seeing at most 3 keys each: 1 + 2 + 3 + 3 + 3
    assert afmoe.window_pairs(5, 3) == 12
    assert afmoe.window_pairs(5, 0) == work.causal_pairs(5, 5) == 15
    assert afmoe.window_pairs(5, 5) == afmoe.window_pairs(5, 9) == 15
    assert afmoe.window_pairs(8192, 2048) == (
        work.causal_pairs(8192, 8192) - work.causal_pairs(6144, 6144))


def test_forward_flops_hand_count():
    z = afmoe.sizes(SMALL)
    assert z.windows == (3, 3, 3, 0, 3, 3)
    # attention: q, o and gate 8x8 each, k and v 8x4 each
    assert afmoe.attn_params(z) == 3 * 64 + 2 * 32
    # a token: 6 attentions, 2 dense MLPs (3 x 8x16), and in each of 4
    # expert layers 2 routed + 1 shared experts (3 x 8x4) and the 8x4
    # router; the 32 x 8 head
    per_token = 6 * 256 + 2 * 384 + 4 * (3 * 96 + 32) + 256
    assert afmoe.token_params(z) == per_token == 3840
    mm = 2 * per_token * 3 * 5
    # L * 4 * B * H * pairs * hd: five sliding layers (12 pairs) and one
    # full layer (15 pairs) over 5 tokens
    attn = 4 * 3 * 2 * (5 * 12 + 15) * 4
    assert afmoe.forward_flops(z, 3, 5) == mm + attn == 122400


def test_serve_tick_counts_windowed_pairs_and_cache():
    z = afmoe.sizes(SMALL)
    flops, nbytes = afmoe.serve_tick(z, [(1, 5)])
    # one new token at position 4: 3 keys on a sliding layer, 5 on the full
    want = 2 * (3840 - 256) + 2 * 256 + 4 * 2 * (5 * 3 + 5) * 4
    assert flops == want
    kv = (5 * 3 + 5) * 2 * 1 * 4 * 2
    weights = (6 * 256 + 2 * 384 + 4 * ((4 + 1) * 96 + 32) + 2 * 256) * 2
    assert nbytes == weights + kv


def _row(result, operands, count, device_s, kind="unknown"):
    return {"kind": kind, "result": result, "operands": operands,
            "count": count, "device_s": device_s, "flops": 0, "bytes": 0}


def test_grouped_roofline_reads_the_grouped_rows_of_a_trace():
    pk = peak()
    # scalar operands first (visit count, offsets, visits' groups and row
    # tiles), then x (m, k) and w (g, k, n); result (m, n)
    row = _row([1024, 256], [[], [9], [15], [15], [1024, 512], [8, 512, 256]],
               4, 1e-3)
    dense = _row([1024, 256], [[1024, 512], [512, 256]], 2, 5e-3, "dense")
    flops = 2 * 1024 * 512 * 256
    nbytes = (1024 * 512 + 8 * 512 * 256 + 1024 * 256) * 2
    assert grouped_roofline.grouped_work(row) == (flops, nbytes)
    assert grouped_roofline.grouped_work(dense) is None
    obs = {"job": "prefill", "peak": pk, "trace": {"pallas": [row, dense]}}
    want = 100 * 4 * max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]) / 1e-3
    assert grouped_roofline.read(obs) == pytest.approx(want, rel=1e-12)


def test_grouped_roofline_is_silent_without_a_grouped_kernel():
    dense = _row([1024, 256], [[1024, 512], [512, 256]], 2, 5e-3, "dense")
    obs = {"job": "prefill", "peak": peak(), "trace": {"pallas": [dense]}}
    assert grouped_roofline.read(obs) is None
    assert grouped_roofline.read({"job": "prefill", "peak": peak()}) is None
    assert grouped_roofline.read({"job": "serve", "trace": {"pallas": []}}) is None


def test_sparse_expert_prefill_serves_tuned_grouped_kernels_closer_than_int8():
    """The prefill job's set-up and window on the trinity-mini
    configuration shrunk for the CPU: the grouped expert tasks are tuned
    (Pallas in interpret mode) and served, and the timed path's logits
    lie closer to the float32 reference than the int8 control's do.  (At
    width 64 with 2 of 8 experts a token, one flipped routing choice moves
    a token's logits far more than at the cell's size, so the cell's own
    limits are not what this size is held to.)"""
    wl = harness.load_json(CHIP / "workloads" / "trinity-mini.prefill-packed-8k.json")
    wl["tuning"].update(ops=["grouped_dense"], max_tasks=2, trials_per_task=2)
    conf = small_conf("trinity-mini")
    r = bench_run.Run(
        name="trinity-mini.prefill-packed-8k", workload=wl, conf=conf,
        traffic={"kind": "packed", "batch": 2, "seq": 32}, seed=2**31 + 17,
        seconds=1.0, trace=False, peak=peak(), backend="pallas-interpret",
    )
    r.obs.update(job="prefill", peak=r.peak)
    job = harness.load_module(CHIP / "jobs" / "prefill.py")
    st = job.setup(r)
    assert sorted(k.split("/")[0] for k in r.obs["tune"]["tasks"]) == [
        "grouped_dense", "grouped_dense"]
    assert r.obs["dispatch"]["hits"] >= 12  # 3 grouped calls x 4 layers
    _, kept = job.steps(r, st, 0, count=2, keep=2, seed=r.seed)
    got = job.compare(r, st, kept, "int8")
    assert got["bad"] == 0
    assert got["logits_rel_err"] < got["control_rel_err"]
    assert got["logits_kl"] < got["control_kl"]
