"""Tensor-program autotuning with probabilistic programs, on JAX/Pallas.

The public surface, importable straight off the package::

    import repro

    result = repro.tune_workload(
        "dense", {"m": 256, "n": 256, "k": 256},
        config=repro.TuneConfig(runner_spec="pool://workers=4"),
        database=repro.Database("tune.json"),
    )
    with repro.DispatchContext(result.database):
        ...  # model forward — tuned kernels served by workload key

Everything here is a lazy re-export (PEP 562): importing ``repro`` stays
cheap (no jax import) until a symbol is actually touched.  The deeper
modules remain importable directly — this is a front door, not a wall.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

# public name -> defining module (relative to this package)
_EXPORTS = {
    # tuning front door
    "tune_workload": "search.tune",
    "apply_best": "search.tune",
    "TuneConfig": "search.tune",
    "TuneResult": "search.tune",
    "SearchConfig": "search.evolutionary",
    # multi-task tuning
    "TaskScheduler": "search.task_scheduler",
    "TuneTask": "search.task_scheduler",
    "extract_tasks": "integration.extract",
    # persistence + serving
    "Database": "search.database",
    "DispatchContext": "integration.dispatch",
    "ServeConfig": "serving.config",
    # measurement fleet
    "create_runner": "search.measure",
    "as_runner": "search.measure",
    "runner_names": "search.measure",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    try:
        modname = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    import importlib

    return getattr(importlib.import_module(f".{modname}", __name__), name)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))


if TYPE_CHECKING:  # static-analysis view of the lazy exports
    from .integration.dispatch import DispatchContext  # noqa: F401
    from .integration.extract import extract_tasks  # noqa: F401
    from .search.database import Database  # noqa: F401
    from .search.evolutionary import SearchConfig  # noqa: F401
    from .search.measure import (  # noqa: F401
        as_runner,
        create_runner,
        runner_names,
    )
    from .search.task_scheduler import TaskScheduler, TuneTask  # noqa: F401
    from .serving.config import ServeConfig  # noqa: F401
    from .search.tune import (  # noqa: F401
        TuneConfig,
        TuneResult,
        apply_best,
        tune_workload,
    )
