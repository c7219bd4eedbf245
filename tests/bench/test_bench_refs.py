"""Reference modules: the dense decoder's step counts against hand counts,
its weights against checksums recorded before it moved under ``refs/``,
and how the harness finds a configuration's module and holds the program
to it."""

import jax
import numpy as np
import pytest
from benchlib import CHIP, ROOT, small_conf

import harness

BENCH = harness.benchmark()

dense = harness.reference({"name": "dense", "reference": "dense_decoder"})

SMALL = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 32,
         "rope_theta": 10000, "rms_norm_eps": 1e-6}


def test_forward_flops_hand_count():
    z = dense.sizes(SMALL)
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 = 64+32+32+64+384
    assert dense.layer_params(z) == 576
    tokens = 3 * 4
    mm = 2 * (2 * 576 + 32 * 8) * tokens
    attn = 2 * 4 * 3 * 2 * 10 * 4  # L * 4 * B * H * pairs(4, 4) * hd
    assert dense.forward_flops(z, 3, 4) == mm + attn
    assert dense.weight_bytes(z) == (2 * 576 + 256) * 2


def test_serve_tick_counts_real_tokens_and_live_cache():
    import work

    z = dense.sizes(SMALL)
    flops, nbytes = dense.serve_tick(z, [(1, 10), (3, 3)])
    per_tok = 2 * 2 * 576
    want = per_tok * 4 + 2 * 2 * 32 * 8
    want += 2 * 4 * 2 * (work.causal_pairs(1, 10) + work.causal_pairs(3, 3)) * 4
    assert flops == want
    kv_tok = 2 * 2 * 1 * 4 * 2
    assert nbytes == dense.weight_bytes(z) + 13 * kv_tok


# (shape, dtype, sum, sum of squares) in float64 of each leaf of the
# weights at the small size with seed 3, as the dense decoder's weights
# were made before its module moved under refs/ (model_ref.make_params).
# A single bf16 rounding step in one element moves a sum of squares by
# more than 1e-6 of it, so the tolerance below still asks for the same
# bits; it leaves room only for numpy's order of summation.
CHECKSUMS = {
    "['embed']": ((256, 48), "bfloat16", -13.39435625076294, 253.44216133255657),
    "['final_ln']": ((48,), "float32", 47.84450560808182, 47.887751877619266),
    "['layers']['attn']['wk']": ((2, 48, 16), "bfloat16", 5.009603023529053, 33.82558099559469),
    "['layers']['attn']['wo']": ((2, 48, 48), "bfloat16", 5.080259203910828, 96.42326005450333),
    "['layers']['attn']['wq']": ((2, 48, 48), "bfloat16", 5.683952331542969, 94.5213833329035),
    "['layers']['attn']['wv']": ((2, 48, 16), "bfloat16", 1.1443300247192383, 30.324991084654357),
    "['layers']['ln1']": ((2, 48), "float32", 96.3344834446907, 97.01669035844907),
    "['layers']['ln2']": ((2, 48), "float32", 96.85429263114929, 98.02685098217663),
    "['layers']['mlp']['wg']": ((2, 48, 96), "bfloat16", -14.290902137756348, 190.8493458747257),
    "['layers']['mlp']['wi']": ((2, 48, 96), "bfloat16", -1.651239275932312, 191.7817094251031),
    "['layers']['mlp']['wo']": ((2, 96, 48), "bfloat16", 9.173497438430786, 94.28500436550092),
}


def test_dense_weights_match_the_checksums_recorded_before_the_move():
    params = dense.make_params(dense.sizes(small_conf()), 3)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert sorted(jax.tree_util.keystr(p) for p, _ in leaves) == sorted(CHECKSUMS)
    for path, leaf in leaves:
        shape, dtype, total, squares = CHECKSUMS[jax.tree_util.keystr(path)]
        a = np.asarray(leaf, np.float64)
        assert (leaf.shape, str(leaf.dtype)) == (shape, dtype)
        assert float(a.sum()) == pytest.approx(total, rel=1e-12, abs=1e-12)
        assert float((a * a).sum()) == pytest.approx(squares, rel=1e-12)


def test_an_unknown_reference_names_the_file_it_looked_for():
    conf = dict(small_conf(), reference="no_such_architecture")
    with pytest.raises(FileNotFoundError) as err:
        harness.program_config(conf)
    assert str(CHIP / "refs" / "no_such_architecture.py") in str(err.value)


def test_a_file_without_a_reference_is_refused():
    conf = small_conf()
    del conf["reference"]
    with pytest.raises(KeyError):
        harness.reference(conf)


@pytest.mark.parametrize("field, value", [
    ("n_layers", 3), ("d_ff", 64), ("norm_eps", 1e-6), ("rope_theta", 5e5),
])
def test_program_config_refuses_a_program_the_reference_does_not_describe(
        field, value):
    conf = small_conf()
    conf["overrides"] = dict(conf["overrides"], **{field: value})
    with pytest.raises(ValueError, match=field):
        harness.program_config(conf)


INTERFACE = ("sizes", "program_fields", "make_params", "forward",
             "forward_flops", "serve_tick", "small")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_names_a_module_with_the_interface(entry):
    conf = harness.load_json(ROOT / entry["file"])
    ref = harness.reference(conf)
    for name in INTERFACE:
        assert callable(getattr(ref, name)), name
    z = ref.sizes(conf)
    assert hash(z) == hash(ref.sizes(conf)) and z.V > 0
    assert harness.reference(conf) is ref  # loaded once
