"""Pallas kernels of the prefill program in the traced window: the least
time their shapes need on this chip (bf16 bytes, work.kernel_work) over
the device time they took, in percent."""

import harness


def read(obs):
    return harness.for_job(obs, "prefill", harness.pallas_roofline)
