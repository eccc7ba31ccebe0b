"""Trace-time program analysis core ("Program Doctor").

Reference analog: the reference lowers every train step to a ProgramDesc and
runs PIR passes + op sanity checks over it BEFORE execution (SURVEY.md §3.3).
Our XLA path has no such gate — a wrong collective axis or a misaligned
Pallas block surfaces as a cryptic compile error or, worse, a silently slow
program. This module recovers the gate: `jax.make_jaxpr` traces the function
(no device execution, works under JAX_PLATFORMS=cpu), and registered rules
walk the jaxpr emitting structured Findings.

Trace recovery: a collective over an axis bound by no mesh raises NameError
at trace time. We catch it, bind the missing axis with size 1, record it in
``ProgramInfo.unbound_axes`` (the collective-axis rule turns that into an
ERROR finding), and retrace — so ONE bad axis doesn't hide every other lint.
"""
from __future__ import annotations

import itertools
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
from jax.extend import core as jcore

from .findings import Report
from .registry import Rule, resolve_rules

_UNSET = object()
_MAX_TRACE_RETRIES = 16


# ---------------------------------------------------------------------------
# program model
# ---------------------------------------------------------------------------

@dataclass
class ProgramInfo:
    """One traced program plus the metadata rules need."""

    closed_jaxpr: Any                      # jax.extend.core.ClosedJaxpr
    mesh: Any = None                       # jax.sharding.Mesh or None
    axis_env: Dict[str, int] = field(default_factory=dict)
    unbound_axes: List[str] = field(default_factory=list)
    donate_argnums: Tuple[int, ...] = ()
    donated_invars: List[Any] = field(default_factory=list)  # jaxpr Vars
    args: tuple = ()                       # post-Tensor-conversion leaves' args
    kwargs: dict = field(default_factory=dict)
    static_args: Dict[str, Any] = field(default_factory=dict)
    context: Dict[str, Any] = field(default_factory=dict)
    target: str = ""

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr

    def axis_size(self, name: str) -> Optional[int]:
        if name in self.axis_env:
            return int(self.axis_env[name])
        if self.mesh is not None and name in self.mesh.axis_names:
            return int(dict(self.mesh.shape)[name])
        return None


# ---------------------------------------------------------------------------
# jaxpr walking helpers (shared by rules)
# ---------------------------------------------------------------------------

def eqn_subjaxprs(eqn) -> List[Any]:
    """Jaxprs nested in an eqn's params (pjit/scan/cond/pallas_call/...)."""
    out: List[Any] = []

    def visit(v):
        if isinstance(v, jcore.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jcore.Jaxpr):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                visit(x)

    for v in eqn.params.values():
        visit(v)
    return out


def iter_eqns(closed_or_jaxpr) -> Iterable[Tuple[int, Any]]:
    """Depth-first (index, eqn) walk into every nested jaxpr."""
    jaxpr = getattr(closed_or_jaxpr, "jaxpr", closed_or_jaxpr)
    counter = itertools.count()

    def walk(j):
        for eqn in j.eqns:
            yield next(counter), eqn
            for sub in eqn_subjaxprs(eqn):
                yield from walk(sub)

    yield from walk(jaxpr)


def eqn_source(eqn) -> str:
    """'file.py:123 (fn)' provenance, best-effort across jax versions."""
    try:
        from jax._src import source_info_util

        return str(source_info_util.summarize(eqn.source_info))
    except Exception:
        return ""


def aval_of(v):
    return getattr(v, "aval", None)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def _deep_unwrap(x):
    """Tensor leaves -> raw jax arrays; everything else unchanged."""
    from ..core.tensor import Tensor

    return jax.tree_util.tree_map(
        lambda v: v._value if isinstance(v, Tensor) else v,
        x, is_leaf=lambda v: isinstance(v, Tensor))


def trace_program(
    fn,
    *args,
    mesh=_UNSET,
    axis_env: Optional[Dict[str, int]] = None,
    donate_argnums: Tuple[int, ...] = (),
    static_args: Optional[Dict[str, Any]] = None,
    context: Optional[Dict[str, Any]] = None,
    target: str = "",
    **kwargs,
) -> ProgramInfo:
    """Trace `fn(*args, **kwargs)` to a jaxpr with NO device execution."""
    if mesh is _UNSET:
        from ..distributed.mesh import get_mesh

        mesh = get_mesh()
    env: Dict[str, int] = {}
    if mesh is not None:
        env.update({str(k): int(v) for k, v in dict(mesh.shape).items()})
    # axis_env: {"dp": 8} or jax-style [("dp", 8), ...]
    pairs = axis_env.items() if hasattr(axis_env, "items") else (axis_env or ())
    env.update({str(k): int(v) for k, v in pairs})

    conv_args = tuple(_deep_unwrap(a) for a in args)
    conv_kwargs = {k: _deep_unwrap(v) for k, v in kwargs.items()}

    unbound: List[str] = []
    closed = None
    for _ in range(_MAX_TRACE_RETRIES):
        try:
            closed = jax.make_jaxpr(
                fn, axis_env=[(k, v) for k, v in env.items()],
            )(*conv_args, **conv_kwargs)
            break
        except NameError as e:
            # "Found an unbound axis name: dp. To fix this, ..."
            m = re.search(r"unbound axis name:?\s*([\w.]*\w)", str(e))
            if not m or m.group(1) in env:
                raise
            ax = m.group(1)
            unbound.append(ax)
            env[ax] = 1  # bind so the rest of the program still traces
    if closed is None:
        raise RuntimeError(
            f"lint trace of {target or fn!r} did not converge after "
            f"{_MAX_TRACE_RETRIES} axis-binding retries (axes: {unbound})")

    # map donated positional args to their jaxpr invars (args flatten first,
    # kwargs after — matching jax's (args, kwargs) in_tree order)
    donated_invars: List[Any] = []
    if donate_argnums:
        offsets = []
        off = 0
        for a in conv_args:
            n = len(jax.tree_util.tree_leaves(a))
            offsets.append((off, off + n))
            off += n
        invars = closed.jaxpr.invars
        for i in donate_argnums:
            if 0 <= i < len(offsets):
                lo, hi = offsets[i]
                donated_invars.extend(invars[lo:hi])

    return ProgramInfo(
        closed_jaxpr=closed,
        mesh=mesh,
        axis_env=env,
        unbound_axes=unbound,
        donate_argnums=tuple(donate_argnums),
        donated_invars=donated_invars,
        args=conv_args,
        kwargs=conv_kwargs,
        static_args=dict(static_args or {}),
        context=dict(context or {}),
        target=target,
    )


# ---------------------------------------------------------------------------
# analysis drivers
# ---------------------------------------------------------------------------

def analyze_program(program: ProgramInfo, rules=None) -> Report:
    """Run registered rules over an already-traced program."""
    report = Report(target=program.target)
    # a rule that raises is a broken analyzer, not a clean program: let it
    # propagate (the CLI exits nonzero; TrainStep's lint gate warns)
    for rule in resolve_rules(rules):
        report.extend(rule.check(program) or ())
    return report.sort()


def analyze(fn, *args, rules=None, **kwargs) -> Report:
    """Trace `fn` and lint it. kwargs: mesh=, axis_env=, donate_argnums=,
    static_args=, context=, target=, plus `fn`'s own keyword args."""
    opt = {k: kwargs.pop(k) for k in
           ("mesh", "axis_env", "donate_argnums", "static_args", "context",
            "target") if k in kwargs}
    program = trace_program(fn, *args, **opt, **kwargs)
    return analyze_program(program, rules=rules)


def analyze_jaxpr(closed_jaxpr, mesh=_UNSET, rules=None, target="",
                  **meta) -> Report:
    """Lint a pre-traced ClosedJaxpr (e.g. from TrainStep.lower())."""
    if mesh is _UNSET:
        from ..distributed.mesh import get_mesh

        mesh = get_mesh()
    program = ProgramInfo(closed_jaxpr=closed_jaxpr, mesh=mesh,
                          target=target, **meta)
    if mesh is not None:
        program.axis_env.update(
            {str(k): int(v) for k, v in dict(mesh.shape).items()})
    return analyze_program(program, rules=rules)


def lint_train_step(step, batch, rules=None, target=None) -> Report:
    """Lint a jit.trainer.TrainStep's program against its mesh/donation
    config without compiling or executing it. `batch` is the positional
    batch (Tensors or arrays) the step will be called with."""
    import jax.numpy as jnp

    batch_vals = _deep_unwrap(tuple(batch))
    args = (
        [p._value for p in step.params],
        [b._value for b in step.buffers],
        step.opt_state,
        jnp.zeros((), jnp.float32),   # lr
        jnp.zeros((), jnp.int32),     # seed
        batch_vals,
    )
    mesh = step._mesh
    if mesh is None:
        from ..distributed.mesh import get_mesh

        mesh = get_mesh()
    env = {}
    if step._dp_axis is not None and mesh is not None:
        env[step._dp_axis] = int(dict(mesh.shape)[step._dp_axis])
    return analyze(
        step._step_fn, *args, mesh=mesh, axis_env=env,
        donate_argnums=(0, 1, 2) if step._donate else (),
        context={"train_step": True},
        rules=rules,
        target=target or f"TrainStep({type(step.model).__name__})")
