"""pallas-tiling: validate Pallas kernel tiling before paying compile cost.

Reference analog: the reference validates kernel attrs (op sanity checks)
before dispatch; MPK (arXiv:2512.22219, PAPERS.md) motivates checking kernel
tiling statically. TPU constraints (see /opt/skills/guides/pallas_guide.md):
the VPU/MXU native tile is (sublane x 128) where the minimum sublane count
depends on dtype — f32:(8,128), bf16/f16:(16,128), int8/fp8:(32,128) — and
each core has ~16 MiB of VMEM that must hold the in+out blocks (x2 for the
pipeline's double buffering). A misaligned block compiles (Mosaic pads) but
wastes lanes; an oversized block set fails compile minutes in, on real TPU.

Checks run on `pallas_call` eqns found in the jaxpr — tracing a pallas_call
needs no TPU, so this lints under JAX_PLATFORMS=cpu. `lint_block_shape` is
the direct (non-jaxpr) entry the tests and kernel authors can call.
"""
from __future__ import annotations

import os
import re
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..analyzer import ProgramInfo, eqn_source, iter_eqns
from ..findings import Finding, Severity
from ..registry import register_rule

VMEM_BYTES = 16 * 1024 * 1024  # fallback per-core budget (v4/v5e class)
_VMEM_WARN_FRACTION = 0.75

_vmem_cached: Optional[int] = None


def vmem_limit_bytes(refresh: bool = False) -> int:
    """Per-core VMEM budget the block estimate is checked against.

    Resolution order — most explicit wins:
      1. PALLAS_VMEM_BYTES env var (tests / odd topologies);
      2. --xla_tpu_scoped_vmem_limit_kib inside XLA_FLAGS (the knob real
         runs use to re-split VMEM between Mosaic and XLA);
      3. a vmem section in the local device's memory_stats() when the
         backend reports one (real TPU runtimes);
      4. the 16 MiB VMEM_BYTES fallback (lint must work on CPU hosts where
         none of the above exists).
    """
    global _vmem_cached
    if _vmem_cached is not None and not refresh:
        return _vmem_cached
    limit = None
    env = os.environ.get("PALLAS_VMEM_BYTES")
    if env:
        try:
            limit = int(env)
        except ValueError:
            limit = None
    if limit is None:
        m = re.search(r"--xla_tpu_scoped_vmem_limit_kib=(\d+)",
                      os.environ.get("XLA_FLAGS", ""))
        if m:
            limit = int(m.group(1)) * 1024
    if limit is None:
        try:
            import jax

            dev = jax.local_devices()[0]
            stats = dev.memory_stats() or {}
            for key in ("vmem_size_bytes", "bytes_limit_vmem", "vmem_limit"):
                if stats.get(key):
                    limit = int(stats[key])
                    break
        except Exception:
            limit = None
    if not limit or limit <= 0:
        limit = VMEM_BYTES
    _vmem_cached = limit
    return limit

_SUBLANE_MIN = {
    "float32": 8, "int32": 8, "uint32": 8,
    "bfloat16": 16, "float16": 16,
    "int8": 32, "uint8": 32,
    "float8_e4m3fn": 32, "float8_e5m2": 32,
}
_LANE = 128


def _int_dims(block_shape) -> List[Optional[int]]:
    """Block dims as ints; None for squeezed dims. A traced pallas_call
    carries pl.Blocked(block_size=n) / pl.Squeezed() entries; the direct
    lint_block_shape entry takes the ints / None of a BlockSpec."""
    out = []
    for b in tuple(block_shape):
        b = getattr(b, "block_size", b)
        out.append(int(b) if isinstance(b, (int, np.integer)) else None)
    return out


def lint_block_shape(block_shape: Sequence, dtype,
                     array_shape: Optional[Sequence[int]] = None,
                     ) -> List[Tuple[str, str]]:
    """Direct tiling lint for one BlockSpec. Returns (code, message) pairs.

    Codes: 'lane' / 'sublane' (block not a multiple of the native tile),
    'ragged' (array dim not divisible by block dim -> padded grid steps).
    """
    dt = str(np.dtype(dtype)) if not isinstance(dtype, str) else dtype
    sub_min = _SUBLANE_MIN.get(dt, 8)
    dims = _int_dims(block_shape)
    arr = list(array_shape) if array_shape is not None else [None] * len(dims)
    # align from the right (block specs may omit leading dims)
    arr = [None] * (len(dims) - len(arr)) + arr[-len(dims):] if dims else []
    issues: List[Tuple[str, str]] = []

    def full(i):  # block spans the whole (short) array dim -> Mosaic pads
        return arr[i] is not None and dims[i] == arr[i]

    if dims and dims[-1] is not None:
        if dims[-1] % _LANE != 0 and not full(-1) and dims[-1] != 1:
            issues.append((
                "lane",
                f"last block dim {dims[-1]} is not a multiple of {_LANE} "
                f"(native lane count) for dtype {dt}"))
    if len(dims) >= 2 and dims[-2] is not None:
        if dims[-2] % sub_min != 0 and not full(-2) and dims[-2] != 1:
            issues.append((
                "sublane",
                f"second-to-last block dim {dims[-2]} is not a multiple of "
                f"{sub_min} (min sublane tile for dtype {dt})"))
    for i, (b, a) in enumerate(zip(dims, arr)):
        if b is not None and a is not None and b and a % b != 0:
            issues.append((
                "ragged",
                f"array dim {i} of size {a} is not divisible by block dim "
                f"{b} — the last grid step runs on padding"))
    return issues


def _block_bytes(dims: List[Optional[int]], dtype) -> int:
    n = 1
    for d in dims:
        n *= (d or 1)
    try:
        return n * np.dtype(dtype).itemsize
    except Exception:
        return n * 4


@register_rule(
    "pallas-tiling", "Pallas block/grid tiling vs TPU tile constraints",
    Severity.ERROR, heuristic=True,
    doc="For every pallas_call: block dims must be multiples of the "
        "per-dtype native tile (f32 (8,128), bf16 (16,128), int8/fp8 "
        "(32,128)) unless they span the whole array dim; array dims should "
        "divide by block dims (ragged grids run padded steps); the in+out "
        "blocks x2 (double buffering) must fit the per-core VMEM budget "
        "(PALLAS_VMEM_BYTES / --xla_tpu_scoped_vmem_limit_kib / device "
        "memory_stats when available, 16 MiB fallback).")
def check(program: ProgramInfo):
    vmem_bytes = vmem_limit_bytes()
    for idx, eqn in iter_eqns(program.closed_jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        bms = getattr(gm, "block_mappings", None)
        if not bms:
            continue
        name = eqn.params.get("name", "") or "pallas_call"
        src = eqn_source(eqn)
        total = 0
        for bm in bms:
            ashape = tuple(bm.array_aval.shape)
            adtype = bm.array_aval.dtype
            dims = _int_dims(bm.block_shape)
            total += _block_bytes(dims, adtype)
            for code, msg in lint_block_shape(dims, adtype, ashape):
                yield Finding(
                    rule="pallas-tiling",
                    severity=(Severity.WARNING if code != "ragged"
                              else Severity.WARNING),
                    message=f"{name}: {msg}",
                    primitive="pallas_call", eqn_index=idx, source=src,
                    fix_hint="size blocks to the native tile "
                             "(/opt guide: f32 (8,128), bf16 (16,128)) and "
                             "pad the array once up front if needed")
        est = 2 * total  # the Mosaic pipeline double-buffers every block
        if est > vmem_bytes:
            yield Finding(
                rule="pallas-tiling", severity=Severity.ERROR,
                message=f"{name}: estimated VMEM for blocks is "
                        f"{est / 2**20:.1f} MiB (x2 double buffering) — "
                        f"over the ~{vmem_bytes // 2**20} MiB/core budget; "
                        "this fails at Mosaic compile time on real TPU",
                primitive="pallas_call", eqn_index=idx, source=src,
                fix_hint="shrink block rows (grid over more steps) or "
                         "lower the kernel's block_* parameters")
        elif est > _VMEM_WARN_FRACTION * vmem_bytes:
            yield Finding(
                rule="pallas-tiling", severity=Severity.WARNING,
                message=f"{name}: estimated VMEM for blocks is "
                        f"{est / 2**20:.1f} MiB of ~"
                        f"{vmem_bytes // 2**20} MiB — no headroom for "
                        "scratch/semaphores; compile may still fail",
                primitive="pallas_call", eqn_index=idx, source=src,
                fix_hint="shrink block rows or split the kernel")
