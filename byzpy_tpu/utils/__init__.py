"""Utility subpackage.

Lazy re-exports: submodules here (checkpoint, metrics, training) import
jax at module import time, but some consumers — example launcher
processes, ``utils.platform`` callers that run before the first backend
touch — must be importable before/without the jax backend. Mirrors the lazy
``__getattr__`` pattern of the top-level package.
"""

from typing import Any

_EXPORTS = {
    "stack_gradients": ("trees", "stack_gradients"),
    "unstack_rows": ("trees", "unstack_rows"),
    "train_with_progress": ("training", "train_with_progress"),
    "train_with_progress_async": ("training", "train_with_progress_async"),
    "CheckpointManager": ("checkpoint", "CheckpointManager"),
    "save_checkpoint": ("checkpoint", "save_checkpoint"),
    "restore_checkpoint": ("checkpoint", "restore_checkpoint"),
    "MetricsLogger": ("metrics", "MetricsLogger"),
    "StepTimer": ("metrics", "StepTimer"),
    "trace": ("metrics", "trace"),
}

__all__ = list(_EXPORTS)


def __getattr__(name: str) -> Any:
    try:
        mod_name, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    import importlib

    return getattr(importlib.import_module(f".{mod_name}", __name__), attr)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
