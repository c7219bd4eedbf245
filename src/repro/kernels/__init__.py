# Pallas TPU kernels for the compute hot-spots this paper's technique
# optimizes: the tunable-BlockSpec matmul is the Use-MXU tensorize target
# (paper §6.3); flash attention and the Mamba-2 SSD scan serve the model
# zoo's long-context paths.  ref.py = pure-jnp oracles.
from typing import Dict, Sequence

import jax.numpy as jnp

from . import ref  # noqa: F401


def kernel_metadata(task: str, blocks: Sequence[int], dtype) -> Dict[str, str]:
    """A kernel's ``pallas_call(metadata=...)``: the workload key it was
    lowered for (``""`` outside a tuned record), its block sizes and its
    operand dtype.  Mosaic puts it in the op's ``kernel_metadata``
    frontend attribute, which the profiler's trace prints with the op."""
    return {
        "task": task,
        "blocks": ",".join(str(int(b)) for b in blocks),
        "dtype": jnp.dtype(dtype).name,
    }
