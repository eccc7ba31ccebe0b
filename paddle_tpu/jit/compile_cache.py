"""Persistent XLA compilation cache + AOT fast dispatch.

Reference analogs: the reference's Program cache is in-process only — every
fresh trainer pays full Program->executable build cost. XLA ships a
content-addressed persistent compilation cache (keyed on serialized HLO +
compile options + backend); wiring it up turns the second process launch of
an identical train step into a disk read instead of a multi-second compile.

Two pieces:
  - enable_persistent_cache(): point jax at an on-disk cache directory
    ($JAX_COMPILATION_CACHE_DIR when set, else a fixed directory inside the
    checkout) and drop the "only cache things that took >1s / >64KB"
    thresholds so even bench-sized programs hit it. Idempotent; call it
    before the first compile (entries are written only after enabling).
  - TrainStep AOT fast dispatch (FLAGS_jit_fast_dispatch, jit/trainer.py):
    `jitted.lower(...).compile()` once, then call the compiled executable
    directly — skipping jax.jit's per-call python dispatch (signature
    hashing, cache probing) on the hot path. Falls back to the normal jit
    callable if the input signature ever changes.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import jax
from jax.experimental.compilation_cache import compilation_cache as _jax_cc

from ..core import flags
from ..observability.registry import counter as _obs_counter

flags.define_flag(
    "jit_compile_cache_dir", "",
    "Directory jit.enable_persistent_cache() uses when called without one "
    "(and JAX_COMPILATION_CACHE_DIR is not set); empty = the fixed "
    ".jax_cache directory inside the checkout. After the call it holds the "
    "directory in use.")
flags.define_flag(
    "jit_fast_dispatch", False,
    "AOT-compile TrainStep on first call and dispatch the compiled "
    "executable directly, bypassing jax.jit python dispatch overhead.")

_enabled_dir: Optional[str] = None

# The path is part of a cache entry's key, so a directory that moves never
# hits: the default is one fixed place inside the checkout (.gitignore'd),
# never one made from tempfile, a pid or the time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Enable jax's on-disk compilation cache; returns the directory in use.

    Where it lives: if the environment sets JAX_COMPILATION_CACHE_DIR, jax
    has already read it and that directory is used — this function then sets
    no other, whatever `cache_dir` or the flag say (whoever launches the
    program places the cache). Otherwise `cache_dir`, else
    FLAGS_jit_compile_cache_dir, else DEFAULT_CACHE_DIR. Call it before the
    first compile. Subsequent calls that resolve to the same directory are
    no-ops.
    """
    global _enabled_dir
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = os.path.abspath(
        from_env or cache_dir
        or str(flags.get_flag("jit_compile_cache_dir") or "")
        or DEFAULT_CACHE_DIR)
    if _enabled_dir == cache_dir:
        return cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # default thresholds skip sub-second / small programs — exactly the ones
    # CI and benches compile over and over
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax probes cache eligibility ONCE per process at the first compile; if
    # anything compiled before this call, re-arm the probe so the directory
    # is actually used (no-op when nothing compiled yet)
    _jax_cc.reset_cache()
    _enabled_dir = cache_dir
    flags.set_flags({"jit_compile_cache_dir": cache_dir})
    return cache_dir


def cache_dir() -> Optional[str]:
    return _enabled_dir


# -- observability (ISSUE r9 satellite): compile-cache hit/miss/evict -------
# counters, registered with the same registry autotune's stats live in.
# `always=True`: these back the cache_info() contract, which must keep
# counting with FLAGS_metrics off (same rule as autotune._STATS).
_EVENTS = _obs_counter(
    "jit_compile_cache_events_total",
    "TrainStep compile events by outcome: hit = persistent cache served the "
    "executable, miss = full XLA compile, evict = AOT executable replaced "
    "on an input-signature change.",
    labelnames=("event",), always=True)

_HIT_TIME_S = 0.5  # compiles faster than this with a live cache dir = hit


def _dir_entries(d: str) -> int:
    try:
        return len(os.listdir(d))
    except OSError:
        return -1


def note_compile(seconds: float, entries_before: Optional[int] = None
                 ) -> str:
    """Record one TrainStep compile; classify persistent-cache hit vs miss.

    With a persistent cache dir live, a MISS writes a new cache entry, so
    entry-count growth (entries_before vs now) is authoritative; callers who
    didn't probe beforehand fall back to the compile-time heuristic (cache
    hits deserialize in well under _HIT_TIME_S). Without a cache dir every
    compile is a miss by definition. Returns the classification.
    """
    event = "miss"
    if _enabled_dir:
        if entries_before is not None and entries_before >= 0:
            after = _dir_entries(_enabled_dir)
            if after >= 0 and after <= entries_before:
                event = "hit"
        elif 0.0 < float(seconds) < _HIT_TIME_S:
            event = "hit"
    _EVENTS.inc(event=event)
    return event


def note_evict() -> None:
    """An AOT executable was dropped (input-signature change)."""
    _EVENTS.inc(event="evict")


def entries_probe() -> Optional[int]:
    """Current persistent-cache entry count (None when cache disabled) —
    pass to note_compile(entries_before=...) for exact hit/miss calls."""
    if not _enabled_dir:
        return None
    return _dir_entries(_enabled_dir)


def cache_info() -> Dict[str, object]:
    """Snapshot mirroring autotune.cache_info()'s shape: counters + dir."""
    return {
        "dir": _enabled_dir,
        "hits": int(_EVENTS.value(event="hit")),
        "misses": int(_EVENTS.value(event="miss")),
        "evictions": int(_EVENTS.value(event="evict")),
    }


class _StatsView:
    """Dict-like legacy view over the registry counters (read-only keys
    hits/misses/evictions), so code expecting a stats mapping keeps working."""

    _KEYS = ("hits", "misses", "evictions")

    def __getitem__(self, k: str) -> int:
        info = cache_info()
        if k not in self._KEYS:
            raise KeyError(k)
        return int(info[k])

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def items(self):
        info = cache_info()
        return [(k, int(info[k])) for k in self._KEYS]

    def __repr__(self):
        return f"_StatsView({dict(self.items())})"


_STATS = _StatsView()
