"""The harness refuses to run off a TPU, and drives the rest of a run on
the CPU (Pallas in interpret mode, small configuration) to a ``correct``
that a broken timed path turns false."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest
from benchlib import CHIP, ROOT, peak, small_conf

import harness

harness.setup_paths()
import run as bench_run  # noqa: E402


def test_exits_nonzero_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload",
         "stablelm-3b.prefill-packed", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_exits_nonzero_on_a_device_kind_without_peaks(monkeypatch, capsys):
    import jax

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v0 unknown")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    code = bench_run.main(["--workload", "stablelm-3b.prefill-packed",
                           "--seed", "1", "--seconds", "1"])
    assert code != 0
    assert capsys.readouterr().out.strip() == ""


@pytest.mark.parametrize(
    "entry", harness.benchmark()["configs"], ids=lambda c: c["name"])
def test_reference_matches_the_program_in_float32(entry):
    """The plain reference and the program's forward agree at a small size
    when both run in float32 at the highest precision."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models.registry import build_model

    conf = harness.load_json(ROOT / entry["file"])
    ref = harness.reference(conf)
    conf = ref.small(conf)
    cfg = dataclasses.replace(harness.program_config(conf), dtype="float32")
    z = ref.sizes(conf)
    params = ref.make_params(z, 3)
    toks = np.random.default_rng(0).integers(0, z.V, (2, 24))
    with jax.default_matmul_precision("highest"):
        got = build_model(cfg).forward(
            jax.tree.map(lambda a: a.astype(jnp.float32), params),
            tokens=jnp.asarray(toks),
        )
    want = ref.forward(z, params, toks)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-5


# A serving cell's workload as the serve job reads it, at a size the CPU
# holds; no serving cell is in BENCHMARK.json until its rate and limit
# are read on the chip.
SERVE = {
    "job": "serve",
    "tuning": {"ops": ["dense", "attention_decode"], "max_tasks": 6,
               "trials_per_task": 8, "seed": 0},
    "serving": {"max_slots": 4, "max_seq": 64, "page_size": 16,
                "prefill_chunk": 16, "token_budget": 0},
    "rate": 6.0,
    "check": {"sample_tokens": 300, "drain_s": 60},
    "limits": {"served_gap": 0.5},
    "trace_seconds": 3,
}


def _run(workload, traffic_mix, seconds=1.5, seed=2**31 + 11, conf=None,
         **settings):
    """Drive a cell's job on the CPU: its workload file (or a workload
    given as a dict), shrunk, on ``conf`` (the cell's configuration
    shrunk, where not given)."""
    if isinstance(workload, dict):
        workload, wl = "serve-chat", json.loads(json.dumps(workload))
    else:
        wl = harness.load_json(CHIP / "workloads" / f"{workload}.json")
    wl["tuning"].update(max_tasks=1, trials_per_task=2)
    for key, value in settings.items():
        wl[key] = dict(wl[key], **value) if isinstance(value, dict) else value
    r = bench_run.Run(
        name=workload, workload=wl, conf=conf or small_conf(),
        traffic=traffic_mix, seed=seed, seconds=seconds, trace=False,
        peak=peak(), backend="pallas-interpret",
    )
    r.obs.update(job=wl["job"], peak=r.peak)
    harness.load_module(CHIP / "jobs" / f"{wl['job']}.py").run(r)
    return r


PREFILL = ("stablelm-3b.prefill-packed", {"kind": "packed", "batch": 2, "seq": 32})


def _serve_run():
    mix = harness.load_json(CHIP / "traffic" / "chat-lognormal.json")
    mix["prompt"].update(median=12, min=4, max=40)
    mix["output"].update(median=6, min=2, max=12)
    mix["greedy_share"] = 0.5
    return _run(SERVE, mix, seconds=3.0)


def test_prefill_run_is_correct():
    r = _run(*PREFILL)
    assert r.checks["logits_rel_err"]["value"] < r.checks["logits_rel_err"]["limit"]
    assert bench_run.correct(r) and r.attempted > 0
    metrics = bench_run.read_metrics(harness.benchmark(), r)
    assert metrics["prefill_tok_s"]["value"] > 0 and "setup_s" in metrics


def test_a_reference_module_outside_the_harness_runs_prefill_to_correct(
        tmp_path, monkeypatch):
    """A new architecture's module is a new file only: a copy of the dense
    decoder's that reads the hidden width under another key, in a
    directory of its own, drives the prefill job to correct; so no job or
    harness code reads the dense decoder's keys."""
    src = (CHIP / "refs" / "dense_decoder.py").read_text()
    assert "hidden_size" in src
    (tmp_path / "renamed_width.py").write_text(
        src.replace("hidden_size", "model_width"))
    conf = small_conf()
    conf["model_width"] = conf.pop("hidden_size")
    conf["reference"] = "renamed_width"
    monkeypatch.setattr(harness, "REFS", tmp_path)
    assert harness.reference(conf).__file__ == str(tmp_path / "renamed_width.py")
    r = _run(*PREFILL, conf=conf)
    assert bench_run.correct(r) and r.attempted > 0, r.checks
    assert r.obs["step_flops"] > 0


def test_prefill_answer_altered_is_not_correct(monkeypatch):
    """A token altered where it is produced: the forward's logits for one
    row of every batch are shifted, as a wrong kernel would."""
    from repro.models.registry import Model

    orig = Model.forward

    def altered(self, params, **inputs):
        out = orig(self, params, **inputs)
        return out.at[0, :, 0].add(50.0)

    monkeypatch.setattr(Model, "forward", altered)
    r = _run(*PREFILL)
    assert not bench_run.correct(r)


def test_serve_run_is_correct():
    r = _serve_run()
    assert r.obs["checked_tokens"] > 0
    assert bench_run.correct(r), r.checks
    for name in ("ttft_p95_ms", "tpot_p95_ms", "setup_s", "serve.queue_ms_p95",
                 "serve.tick_ms"):
        reader = harness.load_module(CHIP / "metrics" / f"{name}.py")
        assert reader.read(r.obs) > 0, name


def test_serve_token_altered_is_not_correct(monkeypatch):
    """A served token altered where it is produced: greedy sampling picks
    the runner-up instead of the best logit."""
    from repro.serving.scheduler import ContinuousBatchingScheduler

    orig = ContinuousBatchingScheduler._sample

    def altered(self, logits, temperature):
        if temperature <= 0:
            return int(np.argsort(logits)[-2])
        return orig(self, logits, temperature)

    monkeypatch.setattr(ContinuousBatchingScheduler, "_sample", altered)
    r = _serve_run()
    assert not bench_run.correct(r)
