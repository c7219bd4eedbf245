#!/usr/bin/env python3
"""Record the chip trace that tests/bench/test_bench_program_trace.py reads.

    python3 tests/bench/record_named_trace.py [out.xplane.pb]   # on a TPU

Like record_trace.py, three steps of a Pallas matmul (256 x 512 x 256)
followed by an XLA ``tanh`` inside ``bench.*`` host spans, but the matmul
is bfloat16 and carries the identity the program compiles into a tuned
kernel (name ``dense``, ``kernel_metadata`` with its task, blocks and
dtype), and the 20 ms host sleep after each step runs inside the
program's own ``repro.sample`` span (``repro.obs`` tracing on), with no
``bench.*`` span over it.  Writes ``tests/bench/data/named.xplane.pb``
unless given another path.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import obs  # noqa: E402
from repro.kernels.matmul import matmul  # noqa: E402
from trace_reduce import find_xplane  # noqa: E402

TASK = "dense/k=256/m=256/n=512"


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    x = jnp.ones((256, 256), jnp.bfloat16)
    w = jnp.ones((256, 512), jnp.bfloat16)
    step = jax.jit(lambda x, w: jnp.tanh(matmul(
        x, w, block_sizes=(128, 128, 128), interpret=False, task=TASK)))
    jax.block_until_ready(step(x, w))
    obs.configure_tracing(sink=obs.RingBufferSink())
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                y = step(x, w)
            with jax.profiler.TraceAnnotation("bench.sync"):
                y.block_until_ready()
            with obs.span("sample"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    obs.disable_tracing()
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else (
        Path(__file__).resolve().parent / "data" / "named.xplane.pb")
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(find_xplane(tmp), out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(out, out.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
