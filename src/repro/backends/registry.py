"""Lowering-backend registry: how a sampled schedule reaches hardware.

MetaSchedule's contract (paper Fig 1, Appendix A.6) is that the
probabilistic space is constructed once and a *backend* carries the
sampled decisions to an executable.  This module makes that backend a
first-class, pluggable object — mirroring the runner registry in
:mod:`repro.search.measure.registry` — so the measurement stack builds
candidates, and the dispatch layer serves models, through the *same*
selected lowering::

    "jnp"               structural jnp lowering (CPU measurement substrate)
    "pallas"            Pallas kernels compiled by Mosaic; raises unless
                        JAX's default backend is a TPU
    "pallas-interpret"  Pallas kernels in the Pallas interpreter (CPU tests)

Selection flows either explicitly (``backend="pallas"`` through
``tune_workload`` / ``TaskScheduler`` / ``DispatchContext`` / the
benchmark CLIs) or ambiently via the ``REPRO_BACKEND`` environment
variable, which every entry point treats as the default.

Plugging in a new backend (e.g. a GPU pallas or multi-device lowering)::

    @register_backend("pallas-gpu")
    def _make():
        return MyGpuBackend()

after which ``REPRO_BACKEND=pallas-gpu`` (or ``backend="pallas-gpu"``)
drives measurement and dispatch without touching either subsystem.
"""

from __future__ import annotations

import abc
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ..core.schedule import Schedule

DEFAULT_BACKEND = "jnp"

_BACKENDS: Dict[str, Callable[[], "Backend"]] = {}
_INSTANCES: Dict[str, "Backend"] = {}


def default_backend_spec() -> str:
    """The ambient backend spec: ``REPRO_BACKEND`` env var or ``"jnp"``."""
    return os.environ.get("REPRO_BACKEND", DEFAULT_BACKEND) or DEFAULT_BACKEND


def resolve_backend_spec(spec: Optional[str]) -> str:
    """``None``/empty -> the ambient default; anything else unchanged."""
    return spec if spec else default_backend_spec()


@dataclass
class Lowered:
    """A backend-lowered schedule: executable + lowering provenance.

    ``fn`` is ``callable(dict inputs) -> dict outputs`` (jit-able);
    ``meta`` is a flat JSON-able dict recording what the lowering actually
    did (backend name, snapped Pallas block sizes, fallbacks...) and is
    persisted into ``TuningRecord.meta`` by the search and surfaced on
    ``CompiledKernel.meta`` by the dispatch layer.
    """

    fn: Callable[[Dict[str, Any]], Dict[str, Any]]
    meta: Dict[str, Any] = field(default_factory=dict)

    def jit(self):
        import jax

        return jax.jit(self.fn)


class Backend(abc.ABC):
    """Lowers validated schedules to executables."""

    name: str = "backend"

    @abc.abstractmethod
    def lower(self, sch: Schedule, workload_key: str = "") -> Lowered:
        """Lower a schedule; raise on impossibility (caller rejects)."""


def register_backend(name: str):
    def deco(factory: Callable[[], Backend]):
        _BACKENDS[name] = factory
        return factory

    return deco


def backend_names() -> List[str]:
    return sorted(_BACKENDS)


def check_backend_spec(spec: Optional[str] = None) -> str:
    """Resolve ``spec`` and raise ``KeyError`` if no backend has that
    name.  Unlike :func:`get_backend` this builds nothing, so a process
    that only hands the spec to its workers never initializes a device."""
    spec = resolve_backend_spec(spec)
    if spec not in _BACKENDS:
        raise KeyError(
            f"unknown backend {spec!r}; available: {', '.join(backend_names())}"
        )
    return spec


def get_backend(spec: Optional[str] = None) -> Backend:
    """Instantiate (memoized) a backend from a registry spec.

    ``None`` resolves through ``REPRO_BACKEND``; unknown names raise
    ``KeyError`` listing what is available.
    """
    spec = check_backend_spec(spec)
    if spec not in _INSTANCES:
        _INSTANCES[spec] = _BACKENDS[spec]()
    return _INSTANCES[spec]


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------


class JnpBackend(Backend):
    """The structural jnp lowering — the CPU measurement substrate."""

    name = "jnp"

    def lower(self, sch: Schedule, workload_key: str = "") -> Lowered:
        from . import jnp_backend

        lowered = jnp_backend.build(sch)
        return Lowered(lowered.fn, {"backend": self.name})


class PallasBackend(Backend):
    """Pallas-kernel lowering of tuned schedules (dense/bmm/sfm + fused
    attention); workloads without a Pallas lowering fall back to the jnp
    structural lowering so measurement batches never hard-fail on mixed
    task sets (the fallback is recorded in ``Lowered.meta``).

    ``interpret=False`` compiles with Mosaic and refuses to exist off a
    TPU, so a CPU run can never pass itself off as a chip run;
    ``interpret=True`` runs the kernels in the Pallas interpreter.
    """

    name = "pallas"

    def __init__(self, interpret: bool):
        if not interpret:
            import jax

            if jax.default_backend() != "tpu":
                raise RuntimeError(
                    "backend 'pallas' compiles with Mosaic and needs a TPU, "
                    f"but JAX's default backend is {jax.default_backend()!r}; "
                    "use 'pallas-interpret' for the Pallas interpreter"
                )
        self.interpret = interpret

    def supports(self, func) -> bool:
        from . import pallas_backend

        return pallas_backend.supports(func)

    def lower(self, sch: Schedule, workload_key: str = "") -> Lowered:
        from . import jnp_backend, pallas_backend

        if pallas_backend.supports(sch.func):
            fn, meta = pallas_backend.lower_to_pallas(
                sch, interpret=self.interpret, task=workload_key
            )
            return Lowered(fn, {"backend": self.name, **meta})
        lowered = jnp_backend.build(sch)
        return Lowered(
            lowered.fn, {"backend": self.name, "lowered_with": "jnp-fallback"}
        )

    # -- fused ops served directly to the dispatch layer --------------------

    def fused_attention(self, q, k, v, **kwargs):
        """Fused flash-attention (Pallas kernel) for the dispatch layer's
        attention hook; see :meth:`DispatchContext.attention`.

        This is the *untuned* fallback: when the database holds a tuned
        ``attention`` record the dispatch layer serves the fully-lowered
        kernel (db-tuned blocks) and never reaches here.  Blocks snap to
        the aligned divisor of the sequence length nearest the MXU-native
        128 tile — the pre-tuning fixed default.  The kernel visits only
        the (q, kv) block pairs its mask admits: a window's band, one q
        block a row, or a causal triangle folded two q blocks a row
        (:func:`~repro.kernels.flash_attention.visit_schedule`).
        """
        from ..kernels.flash_attention import flash_attention

        return flash_attention(
            q, k, v, block_q=128, block_kv=128, interpret=self.interpret,
            **kwargs,
        )


@register_backend("jnp")
def _make_jnp() -> Backend:
    return JnpBackend()


@register_backend("pallas")
def _make_pallas() -> Backend:
    return PallasBackend(interpret=False)


@register_backend("pallas-interpret")
def _make_pallas_interpret() -> Backend:
    be = PallasBackend(interpret=True)
    be.name = "pallas-interpret"
    return be
