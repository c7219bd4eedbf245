"""Pallas kernels of the serve program in the traced window: the least
time their shapes need on this chip (bf16 bytes, work.kernel_work) over
the device time they took, in percent."""

import harness


def read(obs):
    return harness.for_job(obs, "serve", harness.pallas_roofline)
