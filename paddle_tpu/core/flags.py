"""Runtime flag registry.

Reference: PHI_DEFINE_EXPORTED_* gflags (paddle/phi/core/flags.cc, 91 flags) +
paddle.set_flags/get_flags (python/paddle/fluid/framework.py:7493). One typed
registry with env-var override (FLAGS_xxx), per SURVEY.md §5.6.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass
class _Flag:
    name: str
    default: Any
    value: Any
    doc: str
    type: type
    on_change: Optional[Callable[[Any], None]] = None


_registry: Dict[str, _Flag] = {}
_lock = threading.Lock()


def _coerce(ty, raw):
    if ty is bool:
        if isinstance(raw, str):
            return raw.lower() in ("1", "true", "yes", "on")
        return bool(raw)
    return ty(raw)


def define_flag(name: str, default, doc: str = "", on_change=None):
    ty = type(default)
    value = default
    env = os.environ.get(f"FLAGS_{name}")
    if env is not None:
        value = _coerce(ty, env)
    with _lock:
        _registry[name] = _Flag(name, default, value, doc, ty, on_change)
    return value


def get_flags(names=None):
    if names is None:
        names = list(_registry)
    if isinstance(names, str):
        names = [names]
    return {n: _registry[n].value for n in names}


def get_flag(name: str):
    return _registry[name].value


def set_flags(flags: Dict[str, Any]):
    for name, v in flags.items():
        f = _registry.get(name)
        if f is None:
            raise KeyError(f"Unknown flag {name!r}; known: {sorted(_registry)}")
        f.value = _coerce(f.type, v)
        if f.on_change:
            f.on_change(f.value)


# --- core flags (analogs of the reference's most-used ones) ---
def _sync_debug_nans(on):
    # extend the per-op eager check into COMPILED programs: jax re-runs any
    # jitted computation that produced a NaN in op-by-op mode and raises at
    # the offending primitive (reference: full check_nan_inf instrumentation
    # of generated kernels, paddle/fluid/framework/details/nan_inf_utils)
    import jax

    jax.config.update("jax_debug_nans", bool(on))


define_flag("check_nan_inf", False,
            "Check op outputs for NaN/Inf — eager per-op AND inside compiled "
            "programs (jax_debug_nans).", on_change=_sync_debug_nans)
define_flag("default_seed", 0, "Global RNG seed when none set explicitly.")
define_flag(
    "use_flash_attention", True,
    "Use the Pallas flash-attention kernel on TPU when shapes allow.",
)
define_flag(
    "pallas_interpret", False,
    "Run Pallas kernels in interpreter mode (CPU debugging/CI only — the "
    "interpreter is orders of magnitude slower than the XLA fallback).",
)
define_flag(
    "use_fused_adamw", True,
    "Use the fused Pallas AdamW update on TPU (one kernel over all params).",
)
