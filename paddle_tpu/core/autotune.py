"""Kernel autotuning (reference: paddle/phi/kernels/autotune/ — cache.h
size-bounded caches + switch_autotune.cc step-gated tuning, and the Python
knob paddle.incubate.autotune.set_config).

TPU-native design: a config-tuned kernel is a pure function f(*args, **cfg).
`autotune(candidates)` wraps it so the first call per (shape, dtype) key
times every candidate on the REAL device (compile excluded: one warmup call
per candidate, then timed repeats with block_until_ready) and caches the
winner in a bounded LRU. Tuning is off by default (FLAGS_use_autotune);
when off the first candidate — the hand-picked default — runs, so the
decorator is zero-risk to wrap on.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from collections import OrderedDict
from collections.abc import MutableMapping
from typing import Callable, Dict, Iterable, List, Optional

import jax

from . import flags
from ..observability.registry import counter as _obs_counter

flags.define_flag("use_autotune", False,
                  "Time candidate kernel configs on first use and cache the winner.")
flags.define_flag(
    "autotune_cache_dir", "",
    "Directory for the persistent autotune cache. Empty = in-memory only. "
    "Winners are keyed by (kernel, shapes, dtypes, backend) and survive "
    "process restarts, so a warm start skips candidate timing entirely.")

_CACHE: "OrderedDict[tuple, dict]" = OrderedDict()
_CACHE_SIZE = 512   # max cached autotune decisions (LRU eviction)
_LOCK = threading.Lock()

# persistent layer: key-string -> winner config, lazily loaded per cache dir
_DISK: Optional[Dict[str, dict]] = None
_DISK_DIR: Optional[str] = None
# Stats live in the unified metrics registry (observability/) as the labeled
# counter autotune_cache_events_total{event=...}; _STATS keeps the historical
# mutable-dict contract (`_STATS["hits"] += 1`, iteration, cache_info()
# spreading) as a thin view over it. always=True: these counters predate the
# observability layer and must keep counting with FLAGS_metrics off.
_EVENTS = _obs_counter(
    "autotune_cache_events_total",
    "Autotune decision-cache events: hits, misses, disk_hits, tunes, "
    "disk_errors, evictions.",
    labelnames=("event",), always=True)


class _StatsView(MutableMapping):
    """dict-shaped view over autotune_cache_events_total."""

    _KEYS = ("hits", "misses", "disk_hits", "tunes", "disk_errors",
             "evictions")

    def __getitem__(self, k):
        if k not in self._KEYS:
            raise KeyError(k)
        return int(_EVENTS.value(event=k))

    def __setitem__(self, k, v):
        if k not in self._KEYS:
            raise KeyError(k)
        _EVENTS._set_raw(float(v), (str(k),))

    def __delitem__(self, k):
        raise TypeError("autotune stats keys are fixed")

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def __repr__(self):
        return f"_StatsView({dict(self.items())})"


_STATS = _StatsView()

_CACHE_FILE = "autotune_cache.json"


def clear_cache():
    global _DISK, _DISK_DIR
    with _LOCK:
        _CACHE.clear()
        _DISK = None
        _DISK_DIR = None
        for k in _STATS:
            _STATS[k] = 0


def cache_info():
    with _LOCK:
        return {"entries": len(_CACHE), "keys": list(_CACHE),
                **{k: v for k, v in _STATS.items()}}


def stats_snapshot():
    """cache_info() without the per-entry key list — the form telemetry
    embeds in every step record, so it must stay O(1) in cache size."""
    with _LOCK:
        entries = len(_CACHE)
    return {"entries": entries, **{k: _STATS[k] for k in _StatsView._KEYS}}


def _cache_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, _CACHE_FILE)


def _disk_load(cache_dir: str) -> Dict[str, dict]:
    """Load (lazily, once per dir) the persistent winner table. A corrupt or
    unreadable file degrades to an empty table — tuning reruns, never fails."""
    global _DISK, _DISK_DIR
    if _DISK is not None and _DISK_DIR == cache_dir:
        return _DISK
    table: Dict[str, dict] = {}
    path = _cache_path(cache_dir)
    try:
        with open(path, "r", encoding="utf-8") as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            table = {str(k): v for k, v in raw.items()
                     if isinstance(v, dict)}
        else:
            _STATS["disk_errors"] += 1
    except FileNotFoundError:
        pass
    except (OSError, ValueError, UnicodeDecodeError):
        _STATS["disk_errors"] += 1
    _DISK, _DISK_DIR = table, cache_dir
    return table


def _disk_store(cache_dir: str, key_str: str, cfg: dict):
    """Read-merge-write with an atomic rename, so a crash mid-write never
    leaves a truncated file (concurrent writers lose entries, not files)."""
    table = _disk_load(cache_dir)
    table[key_str] = cfg
    path = _cache_path(cache_dir)
    try:
        os.makedirs(cache_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=cache_dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(table, f, indent=0, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError:
        _STATS["disk_errors"] += 1  # read-only dir etc.: keep going in-memory


def _block(x):
    try:
        jax.block_until_ready(x)
    except Exception:  # non-array outputs
        pass
    return x


def _time_once(fn, args, kwargs, cfg, repeats=3):
    out = fn(*args, **kwargs, **cfg)  # warmup/compile
    _block(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kwargs, **cfg)
    _block(out)
    return (time.perf_counter() - t0) / repeats


def autotune(candidates: Iterable[dict], key_extra: Callable = None):
    """Decorator: tune fn's keyword config over `candidates` per input-shape
    key. First candidate is the default used when tuning is disabled or a
    candidate fails (e.g. a block size the lowering rejects)."""
    cands: List[dict] = list(candidates)

    def deco(fn):
        def wrapper(*args, **kwargs):
            key = (fn.__module__, fn.__qualname__,
                   tuple((tuple(a.shape), str(a.dtype))
                         for a in args if hasattr(a, "shape")),
                   key_extra(*args, **kwargs) if key_extra else None,
                   jax.default_backend())
            traced = any(isinstance(a, jax.core.Tracer) for a in args)
            if traced:
                # inside a jit trace wall-clock timing is meaningless (it
                # would measure trace overhead of abstract values and bake
                # every candidate into the graph): use a cached winner from
                # an eager run if one exists, else the default
                entry = _CACHE.get(key)
                return fn(*args, **kwargs, **(entry or cands[0]))
            if not flags.get_flag("use_autotune"):
                return fn(*args, **kwargs, **cands[0])
            entry = _CACHE.get(key)
            if entry is not None:
                with _LOCK:
                    _STATS["hits"] += 1
                    try:
                        _CACHE.move_to_end(key)
                    except KeyError:
                        pass
                return fn(*args, **kwargs, **entry)
            cache_dir = str(flags.get_flag("autotune_cache_dir") or "")
            key_str = repr(key)
            if cache_dir:
                with _LOCK:
                    disk_cfg = _disk_load(cache_dir).get(key_str)
                # accept only configs a known candidate produced: a stale or
                # hand-edited file must not inject arbitrary kwargs
                if disk_cfg in cands:
                    with _LOCK:
                        _STATS["disk_hits"] += 1
                        _CACHE[key] = disk_cfg
                    return fn(*args, **kwargs, **disk_cfg)
            with _LOCK:
                _STATS["misses"] += 1
            best, best_t = None, None
            for cfg in cands:
                try:
                    t = _time_once(fn, args, kwargs, cfg)
                except Exception:
                    continue  # config invalid for these shapes
                if best_t is None or t < best_t:
                    best, best_t = cfg, t
            if best is None:
                best = cands[0]
            with _LOCK:
                _STATS["tunes"] += 1
                _CACHE[key] = best
                _CACHE.move_to_end(key)
                while len(_CACHE) > _CACHE_SIZE:
                    _CACHE.popitem(last=False)
                    _STATS["evictions"] += 1
                if cache_dir:
                    _disk_store(cache_dir, key_str, best)
            return fn(*args, **kwargs, **best)

        wrapper.__wrapped__ = fn
        wrapper.candidates = cands
        return wrapper

    return deco


def set_config(config: Optional[Dict] = None):
    """paddle.incubate.autotune.set_config parity: {'kernel': {'enable':
    bool, 'tuning_range': ...}} — enable flips FLAGS_use_autotune."""
    if config is None:
        flags.set_flags({"use_autotune": True})
        return
    kernel = config.get("kernel", {})
    if "enable" in kernel:
        flags.set_flags({"use_autotune": bool(kernel["enable"])})
