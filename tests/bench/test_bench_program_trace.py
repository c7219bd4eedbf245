"""What the program's own tracing adds to a traced run (program_trace.py).

The tuning session split by the program's spans, on hand-made events and
on a CPU run of the prefill set-up (Pallas in interpret mode, small
configuration); each Pallas kernel's identity and the idle gaps named by
the program's spans, on a trace recorded on a TPU v5e
(record_named_trace.py): three steps of a bfloat16 dense kernel compiled
with its task, blocks and dtype, each followed by a 20 ms host sleep
inside the program's ``repro.sample`` span."""

import pytest
from benchlib import ROOT, peak, small_conf

import harness
import program_trace as pt
import trace_reduce

harness.setup_paths()
import run as bench_run  # noqa: E402

DATA = ROOT / "tests" / "bench" / "data"
NAMED = str(DATA / "named.xplane.pb")
SMALL = str(DATA / "small.xplane.pb")
TASK = "dense/k=256/m=256/n=512"


def _events():
    """A 10 s tuning session [100, 110] and events around it."""
    return [
        {"ev": "trace.start", "ts": 90.0},
        {"ev": "measure.build", "ts": 95.0, "dur_s": 9.0},  # before it
        {"ev": "measure.build", "ts": 101.0, "dur_s": 0.5},
        {"ev": "measure.compile", "ts": 102.9, "dur_s": 2.0, "span": 3},
        {"ev": "measure.run", "ts": 103.0, "dur_s": 3.2, "compile_s": 2.0,
         "timing_s": 1.0},
        {"ev": "measure.build", "ts": 105.0, "dur_s": 0.25},
        {"ev": "measure.run", "ts": 107.0, "dur_s": 2.1, "compile_s": 1.5,
         "timing_s": 0.5},
        {"ev": "tune.session", "ts": 110.0, "dur_s": 10.0, "span": 1},
        {"ev": "measure.run", "ts": 120.0, "dur_s": 18.0, "compile_s": 9.0,
         "timing_s": 9.0},  # after it
    ]


SPLIT = {"search.build_s": 0.75, "search.compile_s": 3.5,
         "search.timing_s": 1.5, "search.self_s": 4.25}


def test_search_split_sums_measurements_inside_the_session():
    got = pt.search_split(_events())
    assert got["session_s"] == pytest.approx(10.0)
    parts = ("build_s", "compile_s", "timing_s", "self_s")
    assert sum(got[k] for k in parts) == pytest.approx(got["session_s"])


def test_span_totals_count_and_sum_events_inside_the_session():
    got = pt.span_totals(_events())
    assert got["measure.build"] == {"count": 2, "dur_s": pytest.approx(0.75)}
    assert got["measure.run"]["compile_s"] == pytest.approx(3.5)
    assert got["measure.compile"]["count"] == 1
    assert "tune.session" not in got and "trace.start" not in got
    assert pt.span_totals([]) == {}


@pytest.mark.parametrize("metric", sorted(SPLIT))
def test_search_readers(metric):
    obs = {"job": "prefill", "search_split": pt.search_split(_events())}
    assert pt.read(obs, metric) == pytest.approx(SPLIT[metric])


@pytest.mark.parametrize("metric", pt.METRICS)
def test_readers_read_nothing_where_nothing_was_traced(metric):
    untraced = pt.search_split([{"ev": "trace.start", "ts": 1.0}])
    assert untraced is None
    assert pt.read({"job": "prefill", "search_split": untraced}, metric) is None
    assert pt.read({"job": "prefill"}, metric) is None


def _kernel_obs(job="prefill"):
    rows = [
        {"task": "a", "count": 10, "device_s": 1.0},  # 0.1 s a call
        {"task": "b", "count": 4, "device_s": 3.0},   # 0.75 s a call
        {"task": "", "count": 5, "device_s": 5.0},    # no record
        {"task": "c", "count": 1, "device_s": 9.0},   # a record not kept
    ]
    return {"job": job, "kernels": rows,
            "tuned_latency_s": {"a": 0.11, "b": 0.6}}


def test_timing_error_weighs_each_tuned_kernel_by_device_time():
    # a: 10 % off over 1 s, b: 20 % off over 3 s
    got = pt.read(_kernel_obs(), "search.timing_error.prefill")
    assert got == pytest.approx((1.0 * 10 + 3.0 * 20) / 4)


def test_timing_error_needs_a_tuned_prefill_kernel():
    assert pt.read(_kernel_obs("serve"), "search.timing_error.prefill") is None
    obs = dict(_kernel_obs(), tuned_latency_s={})
    assert pt.read(obs, "search.timing_error.prefill") is None


def test_identity_of_a_compiled_op_text():
    text = ('%dense.1 = f32[8,128]{1,0:T(8,128)} custom-call(%x.1), '
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{kernel_metadata={\n"blocks":"8,128",\n"dtype":"float32",\n'
            '"task":"dense/k=8/m=8"\n}}, metadata={op_name="x"}')
    assert pt.identity(text) == ("dense", {
        "blocks": "8,128", "dtype": "float32", "task": "dense/k=8/m=8"})


def test_kernel_identity_is_read_from_the_trace():
    (row,) = pt.kernels(NAMED)
    assert (row["template"], row["task"], row["blocks"], row["dtype"]) == (
        "dense", TASK, "128,128,128", "bfloat16")
    assert row["count"] == 3 and row["device_s"] > 0
    assert row["label"] == "pallas dense 256x256 256x512"


def test_a_kernel_without_identity_has_no_task():
    (row,) = pt.kernels(SMALL)
    assert row["task"] == "" and row["count"] == 3


def test_gaps_are_named_by_the_program_span_over_them():
    longest = pt.gaps(NAMED)[:3]
    assert [name for name, _ in longest] == ["repro.sample"] * 3
    assert all(0.015 < s < 0.03 for _, s in longest)


def test_the_breakdown_still_labels_the_named_kernel_by_shape():
    red = trace_reduce.reduce_trace(NAMED)
    assert red["ops_top"][0][0] == "pallas dense 256x256 256x512"
    (row,) = red["pallas"]
    assert row["kind"] == "dense" and row["count"] == 3


@pytest.mark.parametrize("program_trace", [True, False], ids=["on", "off"])
def test_measure_splits_the_tuning_session_on_the_cpu(program_trace):
    wl = harness.load_json(
        ROOT / "benchmarks" / "chip" / "workloads"
        / "stablelm-3b.prefill-packed.json")
    wl["tuning"].update(max_tasks=1, trials_per_task=2)
    r = bench_run.Run(
        name="stablelm-3b.prefill-packed", workload=wl, conf=small_conf(),
        traffic={"kind": "packed", "batch": 2, "seq": 32}, seed=2**31 + 5,
        seconds=0.0, trace=True, peak=peak(), backend="pallas-interpret",
    )
    got = pt.measure(r, steps=0, program_trace=program_trace)
    (task,) = r.obs["tune"]["tasks"]
    assert task in got["tuned_latency_s"]
    assert got["metrics"]["search.timing_error.prefill"] is None
    if not program_trace:
        assert got["events"] == 0 and got["search_split"] is None
        return
    split = got["search_split"]
    assert split["compile_s"] > 0 and split["timing_s"] > 0
    assert split["session_s"] <= got["search.tune_s"]
    assert sum(split[k] for k in ("build_s", "compile_s", "timing_s",
                                  "self_s")) == pytest.approx(split["session_s"])
    for m in SPLIT:
        assert got["metrics"][m] == pytest.approx(split[m[len("search."):]])
