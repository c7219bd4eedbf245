"""The control, the reference computed with int8 linear layers (one step
below the configurations' bf16) put in the program's place, is judged
not correct by the harness's own check, where the program is correct.
On the CPU at the cell's depth (32 layers) and a fifth of its width; the
errors of both grow with depth, and the readings at the cell's own size
on the chip are in PERF.md."""

import pytest
from benchlib import CHIP, peak, small_conf

import harness

harness.setup_paths()
import readings  # noqa: E402
import run as bench_run  # noqa: E402

SMALLER = dict(num_hidden_layers=32, hidden_size=512, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=1536, vocab_size=2048)
PROGRAM = dict(n_layers=32, d_model=512, n_heads=4, n_kv_heads=4, d_ff=1536,
               vocab=2048, head_dim=128)


def _conf():
    conf = small_conf()
    conf.update(SMALLER)
    conf["overrides"].update(PROGRAM)
    return conf


def _run(workload, mix):
    files = harness.cell_files(harness.benchmark(), workload)
    wl = files["workload"]
    wl["tuning"].update(max_tasks=1, trials_per_task=2)
    return bench_run.Run(
        name=workload, workload=wl, conf=_conf(),
        traffic=mix, seed=5, seconds=1.0, trace=False, peak=peak(),
        backend="pallas-interpret",
    )


@pytest.mark.parametrize("seed", [21, 22])
def test_prefill_control_fails_the_limit(seed):
    r = _run("stablelm-3b.prefill-packed",
             {"kind": "packed", "batch": 2, "seq": 64})
    job = harness.load_module(CHIP / "jobs" / "prefill.py")
    (_, got), = readings.prefill_readings(r, job, [seed], 1, 0)
    limits = r.workload["limits"]
    for name, limit in limits.items():
        r.check(name, got[name], limit)
    assert bench_run.correct(r), r.checks
    for name, limit in limits.items():
        r.check(name, got[name.replace("logits", "control")], limit)
    assert not bench_run.correct(r), r.checks
