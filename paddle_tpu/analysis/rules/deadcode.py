"""dead-output: computation whose results nothing consumes.

Reference analog: the reference's dead-code-elimination PIR pass — except
our goal is to REPORT, not silently delete: in a training step, dead eqns
usually mean a loss term that fell out of the return value, an auxiliary
output that was dropped by a refactor, or a metrics branch that silently
stopped being returned. XLA will DCE them (so they cost nothing at runtime)
— which is exactly why they are invisible without a lint: the program runs
fine, just doesn't compute what the author thinks it computes.
"""
from __future__ import annotations

from typing import Any, Iterable, List, Tuple

from jax.core import DropVar
from jax.extend.core import Var

from ..analyzer import ProgramInfo, eqn_source, eqn_subjaxprs
from ..findings import Finding, Severity
from ..registry import register_rule

# primitives we never report as dead even without live outputs (control flow
# and kernels may act through effects/aliasing the liveness walk can't see)
_KEEP = {"while", "cond", "scan", "pallas_call", "optimization_barrier"}
_KEEP_PREFIX = ("custom_vjp_call", "custom_jvp_call")

# only dead subtrees containing one of these are REPORTED: the eager engine
# records jax.vjp at op dispatch (ops/registry.py), so grad-enabled traces
# legitimately carry cheap dead residual eqns (XLA DCEs them for free) —
# reporting every one would bury the signal. A dropped loss term / dropped
# model output virtually always contains a contraction or structural op.
_HEAVY = {"dot_general", "conv_general_dilated", "sort", "top_k",
          "gather", "scatter", "scatter_add", "fft", "pjit",
          "reduce_window_sum", "reduce_window_max", "cumsum", "cumlogsumexp"}


def _is_var(v) -> bool:
    return isinstance(v, Var) and not isinstance(v, DropVar)


def _dead_eqns(jaxpr) -> List[Tuple[int, Any]]:
    """Indices+eqns in THIS jaxpr whose outputs reach no output/effect."""
    live = {id(v) for v in jaxpr.outvars if _is_var(v)}
    dead: List[Tuple[int, Any]] = []
    for i in reversed(range(len(jaxpr.eqns))):
        eqn = jaxpr.eqns[i]
        name = eqn.primitive.name
        is_live = (
            bool(getattr(eqn, "effects", None))
            or name in _KEEP or name.startswith(_KEEP_PREFIX)
            or any(id(v) in live for v in eqn.outvars)
        )
        if is_live:
            live.update(id(v) for v in eqn.invars if _is_var(v))
        else:
            dead.append((i, eqn))
    dead.reverse()
    return dead


@register_rule(
    "dead-output", "Dead computation / dropped outputs",
    Severity.WARNING, heuristic=True,
    doc="Equations whose results reach no program output and no effect. "
        "Reported at the dead SINKS (the last eqns of each dead subtree) "
        "with the size of the subtree; sub-jaxprs (scan/cond bodies, "
        "pjit) are analyzed independently with their outvars as roots.")
def check(program: ProgramInfo) -> Iterable[Finding]:
    # walk every jaxpr independently; a sub-jaxpr's outvars count as live
    # roots (the outer eqn decides whether THEY are used)
    stack = [program.jaxpr]
    seen = set()
    while stack:
        jaxpr = stack.pop()
        if id(jaxpr) in seen:
            continue
        seen.add(id(jaxpr))
        dead = _dead_eqns(jaxpr)
        # anchor findings at heavyweight dead eqns only — cheap dead residue
        # is expected from the vjp-at-dispatch engine (see _HEAVY above)
        for i, eqn in dead:
            if eqn.primitive.name not in _HEAVY:
                continue
            yield Finding(
                rule="dead-output", severity=Severity.WARNING,
                message=f"result of {eqn.primitive.name} is never used "
                        f"({len(dead)} dead eqn(s) in this jaxpr) — XLA "
                        "deletes it, so whatever it was meant to compute "
                        "is not actually computed",
                primitive=eqn.primitive.name, eqn_index=i,
                source=eqn_source(eqn),
                fix_hint="return the value or delete the computation")
        for eqn in jaxpr.eqns:
            stack.extend(eqn_subjaxprs(eqn))
