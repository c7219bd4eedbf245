# Pallas TPU kernels for the compute hot-spots this paper's technique
# optimizes: the tunable-BlockSpec matmul is the Use-MXU tensorize target
# (paper §6.3); flash attention and the Mamba-2 SSD scan serve the model
# zoo's long-context paths.  ref.py = pure-jnp oracles.
from . import ref  # noqa: F401
