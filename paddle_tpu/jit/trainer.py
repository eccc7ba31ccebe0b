"""Compiled training step.

Reference analog: the static-graph training path — Program capture +
StandaloneExecutor with one fused program per step (SURVEY.md §3.3), plus the
donation/buffer-reuse the reference gets from its allocator. Here: ONE XLA
program computes forward + backward + optimizer update; param and optimizer
state buffers are donated so updates are in-place in HBM.

The autograd inside the trace is the SAME engine as eager (core/autograd.py) —
the dual-mode property the reference engineers via shared phi kernels.
"""
from __future__ import annotations

import time
import warnings
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd as _ag
from ..core import random as _random
from ..core.flags import define_flag, get_flag
from ..core.tensor import Tensor
from ..nn.clip import ClipGradByGlobalNorm
from ..nn.layer import Layer
from ..observability import flight_recorder as _flight
from ..observability import telemetry as _telemetry
from ..observability.spans import NOOP as _NOOP_SPAN
from ..observability.spans import span as _span

define_flag(
    "jit_lint", "off",
    "Static-analysis gate for compiled train steps (analysis/): 'off', "
    "'warn' (lint on first call, emit findings as warnings), or 'raise' "
    "(additionally fail fast on ERROR-severity findings). Trace-only — "
    "adds one make_jaxpr trace before the first compile, nothing per-step.")


def _step_annotation(step: int):
    """While a jax profiler session is live, the step marker its tools
    group device work by; otherwise nothing."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.StepTraceAnnotation("train", step_num=step)
    return _NOOP_SPAN


def _tensor_leaves(x):
    return jax.tree_util.tree_map(
        lambda v: v._value if isinstance(v, Tensor) else v,
        x,
        is_leaf=lambda v: isinstance(v, Tensor),
    )


class TrainStep:
    """Compile forward+backward+update into one donated-buffer XLA program.

    Usage:
        step = TrainStep(model, loss_fn, optimizer)   # loss_fn(*batch)->loss
        loss = step(x, y)                             # runs the compiled step
    """

    def __init__(
        self,
        model: Layer,
        loss_fn: Callable[..., Tensor],
        optimizer,
        donate: bool = True,
        in_shardings=None,
        out_shardings=None,
        mesh=None,
        nan_guard: bool = False,
        dp_axis: Optional[str] = None,
        grad_bucket_mb: Optional[int] = None,
        dp_overlap: Optional[str] = None,
        telemetry: Optional[bool] = None,
    ):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # Per-step telemetry (observability/): when on, the compiled program
        # additionally returns the pre-clip gradient global-norm and __call__
        # emits one step record (loss/gnorm/lr/throughput/phases) through
        # observability.telemetry. Resolved at CONSTRUCTION time because it
        # changes the program's output arity; None follows FLAGS_metrics.
        self._telemetry = (_telemetry.enabled() if telemetry is None
                          else bool(telemetry))
        # NaN/Inf step-guard (resilience subsystem): the finite-check and the
        # where-select between updated and prior state compile INTO this one
        # program, so donation and the single-dispatch property are preserved
        # (the reference's check_finite_and_unscale + found_inf skip, fused).
        self._nan_guard = bool(nan_guard)
        self.skipped_steps = 0
        self.last_skipped = False
        self.params = [p for p in model.parameters() if p.trainable]
        # frozen params ride as runtime inputs like buffers — leaving them
        # out would constant-fold their CURRENT values into the compiled
        # step, silently ignoring later set_state_dict/EMA updates
        self.buffers = [b for b in model.buffers()] +             [p for p in model.parameters() if not p.trainable]
        # copy state leaves: init_state_tree shares arrays with the
        # optimizer's own accumulator store, and donating shared buffers
        # would invalidate optimizer.state_dict() on backends that honor
        # donation (TPU/GPU)
        self.opt_state = jax.tree_util.tree_map(
            jnp.asarray, optimizer.init_state_tree(self.params))
        self.opt_state = jax.tree_util.tree_map(
            lambda x: x.copy() if hasattr(x, "copy") else x, self.opt_state)
        self._mesh = mesh
        self._step_i = 0
        # Explicit data-parallel path: shard_map over `dp_axis` with the
        # gradient all-reduce coalesced into fixed-byte buckets, each bucket
        # its own pmean so XLA's latency-hiding scheduler overlaps it with
        # the remaining backward (distributed/grad_buckets.py). None keeps
        # the implicit GSPMD path (grads reduced wherever XLA places them).
        self._dp_axis = dp_axis
        if grad_bucket_mb is None:
            self._bucket_bytes = None  # resolve from FLAGS at trace time
        else:
            self._bucket_bytes = (int(grad_bucket_mb) << 20
                                  if grad_bucket_mb >= 0 else 1 << 62)
        # Reduction schedule on the explicit-DP path: 'bucketed' keeps one
        # pmean per bucket (bitwise vs single all-reduce); 'fine' lowers each
        # bucket to a decomposed ring reduce-scatter/all-gather interleaved
        # with the backward (distributed/overlap.py; allclose parity). None
        # follows FLAGS_dp_overlap at trace time.
        if dp_overlap is not None:
            dp_overlap = str(dp_overlap).lower()
            if dp_overlap not in ("bucketed", "fine"):
                raise ValueError(
                    f"dp_overlap={dp_overlap!r}: expected 'bucketed' or "
                    "'fine'")
        self._dp_overlap = dp_overlap

        # ZeRO stage placements (distributed/sharding.py): optimizer state is
        # sharded in all stages; grads carry a reduce-scatter constraint in
        # stages 2/3 (params were placed by group_sharded_parallel itself).
        from ..distributed.sharding import zero_grad_sharding, zero_state_sharding

        state_sh = zero_state_sharding(optimizer, self.params)
        if state_sh is not None:
            placed = []
            for st, sh, p in zip(self.opt_state, state_sh, self.params):
                st = dict(st)
                for k, v in st.items():
                    if hasattr(v, "shape") and tuple(v.shape) == tuple(p._value.shape):
                        st[k] = jax.device_put(v, sh)
                placed.append(st)
            self.opt_state = placed
        self._grad_shardings = zero_grad_sharding(optimizer, self.params)
        # pin updated params to their stage placement — otherwise GSPMD
        # propagates the sharded optimizer-state layout onto them, silently
        # turning stage 1/2 (replicated params) into stage 3
        self._param_shardings = (
            [p._value.sharding for p in self.params]
            if getattr(optimizer, "_zero_level", None) else None)

        def fwd_bwd(param_vals, buffer_vals, batch):
            """Pure forward+backward: swap the traced values into the live
            layer tree, differentiate, restore. Returns (loss, per-param
            grads, updated buffer values). Deliberately collective-free so
            the fine overlap scheduler can make_jaxpr it and hand the
            readiness analysis a pure backward."""
            saved = [(p._value, p._grad_node, p._grad, p.stop_gradient)
                     for p in self.params]
            saved_buf = [(b._value,) for b in self.buffers]
            try:
                for p, v in zip(self.params, param_vals):
                    p._value = v
                    p._grad_node = None
                    p._grad = None
                    p.stop_gradient = False
                for b, v in zip(self.buffers, buffer_vals):
                    b._value = v
                batch_t = jax.tree_util.tree_map(Tensor, batch)
                loss = self.loss_fn(*batch_t)
                grads = _ag.grad(loss, self.params, allow_unused=True)
                g_vals = [
                    (g._value if g is not None else jnp.zeros_like(p._value))
                    for g, p in zip(grads, self.params)
                ]
                new_buffer_vals = [b._value for b in self.buffers]  # BN stats updated in-place
                return loss._value, g_vals, new_buffer_vals
            finally:
                for p, (v, gn, g, sg) in zip(self.params, saved):
                    p._value, p._grad_node, p._grad, p.stop_gradient = \
                        v, gn, g, sg
                for b, (v,) in zip(self.buffers, saved_buf):
                    b._value = v

        self._fwd_bwd_fn = fwd_bwd  # overlap tests trace this directly

        # the function's name is the XLA module's in a device trace
        # (jit_train_step), on every path that builds self._jitted
        def train_step(param_vals, buffer_vals, opt_state, lr, seed, batch):
            saved = [(p._value,) for p in self.params]
            prev_seed = _random.default_generator.push_trace_seed(seed)
            try:
                if self._dp_axis is not None and \
                        self._overlap_mode() == "fine":
                    # fine-grained overlap: trace the pure backward, replay
                    # it with each bucket's decomposed ring all-reduce
                    # interleaved at its readiness point
                    # (distributed/overlap.py)
                    from ..distributed import overlap as _overlap

                    loss_val, g_vals, new_buffer_vals = \
                        _overlap.overlap_grad_reduce(
                            fwd_bwd, (param_vals, buffer_vals, batch),
                            self._dp_axis, self._bucket_bytes)
                    loss_val = jax.lax.pmean(loss_val, self._dp_axis)
                else:
                    loss_val, g_vals, new_buffer_vals = fwd_bwd(
                        param_vals, buffer_vals, batch)
                    if self._dp_axis is not None:
                        # explicit DP: bucketed all-reduce BEFORE clipping so
                        # the clip sees globally-reduced grads (GSPMD parity)
                        from ..distributed.grad_buckets import bucket_reduce

                        g_vals = bucket_reduce(g_vals, self._dp_axis,
                                               self._bucket_bytes)
                        loss_val = jax.lax.pmean(loss_val, self._dp_axis)
                # clip/update section: hybrid clips read param identities AND
                # their current (traced) values, so swap those back in
                for p, v in zip(self.params, param_vals):
                    p._value = v
                if self._grad_shardings is not None:  # ZeRO-2/3 reduce-scatter
                    g_vals = [
                        jax.lax.with_sharding_constraint(g, sh)
                        for g, sh in zip(g_vals, self._grad_shardings)
                    ]
                gsq = None
                if self._nan_guard or self._telemetry:
                    # PRE-clip gradient global-norm square-sum: the standard
                    # logged quantity, shared by the step-guard (NaN/Inf is
                    # not repaired by clipping, so checking it pre-clip is
                    # equivalent) and the telemetry gnorm output
                    gsq = jnp.zeros((), jnp.float32)
                    for g in g_vals:
                        gsq = gsq + jnp.sum(jnp.square(
                            g.astype(jnp.float32)))
                with jax.named_scope("optimizer"):  # clip + update
                    clip = optimizer._grad_clip
                    if isinstance(clip, ClipGradByGlobalNorm):
                        import inspect as _inspect

                        if "params" in _inspect.signature(
                                clip.functional_clip).parameters:
                            # hybrid clip: param identities distinguish
                            # tensor-parallel from replicated norms
                            g_vals = clip.functional_clip(g_vals,
                                                          params=self.params)
                        else:
                            g_vals = clip.functional_clip(g_vals)
                    elif clip is not None:
                        pairs = clip([(p, Tensor(g)) for p, g
                                      in zip(self.params, g_vals)])
                        g_vals = [g._value for _, g in pairs]
                    new_p, new_s = optimizer.functional_update(
                        param_vals, g_vals, opt_state, lr)
                if self._param_shardings is not None:
                    new_p = [
                        jax.lax.with_sharding_constraint(v, sh)
                        for v, sh in zip(new_p, self._param_shardings)
                    ]
                out = [loss_val, new_p, new_buffer_vals, new_s]
                if self._nan_guard:
                    # finite check; overflow of the square-sum to inf is
                    # itself a (correct) skip signal
                    ok = jnp.isfinite(gsq) & jnp.isfinite(
                        loss_val.astype(jnp.float32))
                    out[1] = [jnp.where(ok, n, o)
                              for n, o in zip(new_p, param_vals)]
                    out[2] = [jnp.where(ok, n, o)
                              for n, o in zip(new_buffer_vals, buffer_vals)]
                    out[3] = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(ok, n, o), new_s, opt_state)
                    out.append((~ok).astype(jnp.int32))
                if self._telemetry:
                    out.append(jnp.sqrt(gsq))
                return tuple(out)
            finally:
                _random.default_generator.pop_trace_seed(prev_seed)
                for p, (v,) in zip(self.params, saved):
                    p._value = v

        self._step_fn = train_step  # analysis.lint_train_step traces this
        self._donate = bool(donate)
        self._linted = False
        donate_argnums = (0, 1, 2) if donate else ()
        self._dp_size = None
        if dp_axis is not None:
            from jax.sharding import PartitionSpec as _P

            from ..distributed.mesh import get_mesh as _get_mesh

            dp_mesh = mesh if mesh is not None else _get_mesh()
            # same check the collective-axis lint does, enforced at runtime:
            # a missing axis must not surface as a bare KeyError/NameError
            # from deep inside shard_map
            if dp_mesh is None:
                raise ValueError(
                    f"dp_axis={dp_axis!r} needs an active mesh but none is "
                    "set — pass mesh= or call distributed.set_mesh(...) "
                    "(distributed.build_mesh(dp=N) makes one)")
            if dp_axis not in dp_mesh.axis_names:
                sizes = dict(dp_mesh.shape)
                raise ValueError(
                    f"dp_axis={dp_axis!r} is not an axis of the active "
                    f"mesh — available axes and sizes: {sizes}")
            self._dp_size = int(dict(dp_mesh.shape)[dp_axis])
            if self._grad_shardings is not None or \
                    self._param_shardings is not None:
                raise ValueError(
                    "bucketed DP (dp_axis=) and ZeRO stages are mutually "
                    "exclusive — ZeRO's reduce-scatter already overlaps")
            if in_shardings is not None or out_shardings is not None:
                raise ValueError(
                    "dp_axis= replaces in_shardings/out_shardings: the "
                    "shard_map specs define the placement")
            self._mesh = dp_mesh  # resolved mesh, for lint + introspection
            # state replicated over dp, batch split on its leading dim;
            # outputs replicated (grads/loss are pmean'ed inside)
            smapped = jax.shard_map(
                train_step, mesh=dp_mesh,
                in_specs=(_P(), _P(), _P(), _P(), _P(), _P(dp_axis)),
                out_specs=_P(),
                axis_names=frozenset({dp_axis}), check_vma=False)
            self._base_callable = smapped
            self._io_shardings = (None, None)
            self._jitted = jax.jit(smapped, donate_argnums=donate_argnums)
        else:
            self._base_callable = train_step
            self._io_shardings = (in_shardings, out_shardings)
            self._jitted = jax.jit(
                train_step,
                donate_argnums=donate_argnums,
                in_shardings=in_shardings,
                out_shardings=out_shardings,
            )
        self._donate_argnums = donate_argnums
        # AOT fast dispatch (jit/compile_cache.py): the lowered+compiled
        # executable for the (single) input signature, built lazily
        self._aot = None
        self._aot_sig = None
        self._n_params = None  # resolved lazily for the telemetry MFU
        self._batch_dims = None  # (samples, tokens) cached per signature
        # overlap schedule config baked into the traced program (mode,
        # bucket bytes, ring floor): tracked so a FLAGS flip between calls
        # rebuilds the jit cache instead of dispatching the stale trace
        self._overlap_cfg_used = None
        # attributed reduce time (telemetry): the fused program hides the
        # collective wait inside compute_s, so a standalone comm-only probe
        # is compiled lazily and re-timed every ~50 steps
        self._reduce_probe = None
        self._probe_zeros = None
        self._reduce_s = None
        self._probe_step = -(1 << 30)

    def _overlap_mode(self) -> str:
        """Resolved reduction schedule for the dp path: the explicit
        constructor arg wins, else FLAGS_dp_overlap (read at trace time)."""
        mode = self._dp_overlap if self._dp_overlap is not None else \
            str(get_flag("dp_overlap")).lower()
        if mode not in ("bucketed", "fine"):
            raise ValueError(
                f"FLAGS_dp_overlap={mode!r}: expected 'bucketed' or 'fine'")
        return mode

    def _overlap_cfg(self):
        """The schedule-shaping knobs the traced program closed over."""
        from ..distributed.grad_buckets import default_bucket_bytes
        from ..distributed.overlap import min_ring_bytes

        return (self._overlap_mode(),
                self._bucket_bytes if self._bucket_bytes is not None
                else default_bucket_bytes(),
                min_ring_bytes())

    def _refresh_overlap_cfg(self) -> None:
        """jax caches traces on arg signatures only — the overlap flags are
        read at trace time, so a change between calls must drop the cached
        trace (and the AOT executable) to take effect."""
        if self._dp_axis is None:
            return
        cfg = self._overlap_cfg()
        if self._overlap_cfg_used is None:
            self._overlap_cfg_used = cfg
            return
        if cfg != self._overlap_cfg_used:
            self._overlap_cfg_used = cfg
            # jax's trace cache is shared across jit wrappers and keyed on
            # the underlying callable's identity — a fresh closure forces
            # the body (and the flags it reads) to actually re-trace
            base = self._base_callable

            def train_step(*a):
                return base(*a)

            self._jitted = jax.jit(train_step,
                                   donate_argnums=self._donate_argnums)
            self._aot = None
            self._aot_sig = None
            self._reduce_probe = None  # schedule changed: re-probe
            self._reduce_s = None

    def invalidate_executables(self) -> None:
        """Drop every compiled artifact keyed on the current topology: the
        cached trace, the AOT executable + its signature, and the reduce
        probe. Elastic reformation calls this when the world size changes —
        an executable traced (or AOT-compiled) for the old N would either
        silently compute with stale mesh constants or fail on the new
        shard shapes. The next call re-traces against whatever mesh/flags
        are then in effect."""
        base = self._base_callable

        def train_step(*a):
            return base(*a)

        # same fresh-closure trick as _refresh_overlap_cfg: jax's trace
        # cache keys on callable identity, so a new wrapper object is what
        # actually forces the re-trace
        ins, outs = self._io_shardings
        kwargs = {}
        if ins is not None:
            kwargs["in_shardings"] = ins
        if outs is not None:
            kwargs["out_shardings"] = outs
        self._jitted = jax.jit(train_step,
                               donate_argnums=self._donate_argnums,
                               **kwargs)
        self._aot = None
        self._aot_sig = None
        self._reduce_probe = None
        self._probe_zeros = None
        self._reduce_s = None
        self._batch_dims = None

    @staticmethod
    def _arg_signature(args):
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (treedef, tuple(
            (tuple(getattr(v, "shape", ())),
             str(getattr(v, "dtype", type(v).__name__))) for v in leaves))

    def _dispatch(self, *args):
        from ..core.flags import get_flag

        self._refresh_overlap_cfg()
        if not get_flag("jit_fast_dispatch"):
            if not self._telemetry:
                return self._jitted(*args)
            # plain-jit path: infer compile events from tracing-cache growth
            size_fn = getattr(self._jitted, "_cache_size", None)
            before = size_fn() if callable(size_fn) else None
            out = self._jitted(*args)
            if before is not None and callable(size_fn) and \
                    size_fn() > before:
                from . import compile_cache as _cc

                _cc.note_compile(0.0)
                _telemetry.get_telemetry().event(
                    "compile" if before == 0 else "recompile",
                    what="train_step", aot=False)
            return out
        sig = self._arg_signature(args)
        if self._dp_axis is not None:
            # the overlap schedule is part of the compiled program, so it is
            # part of the executable's identity too
            sig = (sig, self._overlap_cfg_used)
        if self._aot is None or sig != self._aot_sig:
            # new shape/dtype signature: AOT-compile for it (first time), or
            # fall through jit for a shape-polymorphic caller
            from . import compile_cache as _cc

            recompile = self._aot is not None
            if recompile:
                _cc.note_evict()  # signature change replaces the executable
                self._batch_dims = None  # new signature: rescan batch shape
            entries = _cc.entries_probe()
            t0 = time.perf_counter()
            with _span("jit.compile", cat="jit"):
                self._aot = self._jitted.lower(*args).compile()
            dt = time.perf_counter() - t0
            self._aot_sig = sig
            _cc.note_compile(dt, entries_before=entries)
            if self._telemetry and _telemetry.enabled():
                _telemetry.get_telemetry().event(
                    "recompile" if recompile else "compile",
                    what="train_step", seconds=round(dt, 4), aot=True)
                # XLA's own accounting of what we just built: compiled
                # peak/temp/code bytes, flops, bytes-accessed (memory.py
                # gauges + `executable` event; never raises)
                from ..observability import memory as _memory

                _memory.note_executable("train_step", self._aot)
        return self._aot(*args)

    def _check_dp_batch(self, batch_vals):
        """Fail with a readable error before shard_map pads or crashes."""
        for leaf in jax.tree_util.tree_leaves(batch_vals):
            shape = tuple(getattr(leaf, "shape", ()))
            if shape and shape[0] % self._dp_size != 0:
                raise ValueError(
                    f"dp_axis={self._dp_axis!r} (size {self._dp_size}) "
                    f"cannot split a batch leaf of shape {shape}: leading "
                    f"dim {shape[0]} is not divisible by {self._dp_size}")

    def _maybe_lint(self, batch):
        """FLAGS_jit_lint: lint-on-first-trace (analysis/), warn or raise."""
        mode = str(get_flag("jit_lint")).lower()
        if mode in ("", "0", "off", "false", "no"):
            return
        from .. import analysis

        try:
            report = analysis.lint_train_step(self, batch)
        except Exception as e:  # lint must never take down training
            warnings.warn(f"FLAGS_jit_lint: lint trace skipped "
                          f"({type(e).__name__}: {e})")
            return
        if mode == "raise":
            report.raise_if(analysis.Severity.ERROR)
        for f in report.findings:
            warnings.warn(f"[jit_lint] {f.format()}")

    def __call__(self, *batch):
        batch_vals = _tensor_leaves(batch)
        param_vals = [p._value for p in self.params]
        buffer_vals = [b._value for b in self.buffers]
        # two eager one-op programs a step (jit_convert_element_type in a
        # device trace): named here so the gap they leave has an owner
        with _span("jit.host_scalars", cat="jit"):
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            seed = jnp.asarray(self._step_i, jnp.int32)
        if not self._linted:
            self._linted = True
            if self._dp_size is not None:
                self._check_dp_batch(batch_vals)
            self._maybe_lint(batch)
        self._step_i += 1
        t0 = time.perf_counter() if self._telemetry else 0.0
        with _span("jit.train_step", cat="jit",
                   args={"step": self._step_i - 1}), _step_annotation(
                       self._step_i - 1):
            out = self._dispatch(
                param_vals, buffer_vals, self.opt_state, lr, seed, batch_vals
            )
        gnorm = None
        if self._telemetry:
            out, gnorm = out[:-1], out[-1]
        if self._nan_guard:
            loss, new_p, new_b, new_s, skipped = out
            n_skipped = int(skipped)  # one host-scalar read, like loss.item()
            self.last_skipped = bool(n_skipped)
            self.skipped_steps += n_skipped
        else:
            loss, new_p, new_b, new_s = out
        for p, v in zip(self.params, new_p):
            p._value = v
        for b, v in zip(self.buffers, new_b):
            b._value = v
        self.opt_state = new_s
        sched = self.optimizer._lr_scheduler
        if sched is not None:
            sched.step()
        self.optimizer._step_count += 1
        if self._telemetry:
            self._emit_step(loss, gnorm, float(lr), t0, batch_vals)
        return Tensor(loss)

    _REDUCE_PROBE_EVERY = 50  # steps between reduce-probe re-measurements

    def _probe_reduce_s(self) -> Optional[float]:
        """Attributed reduce time for telemetry on the explicit-DP path.

        The gradient all-reduce is fused into the one step executable, so no
        host-observable reduce wait exists and `reduce_ms` would read 0.0
        forever. Instead, a standalone program containing ONLY this step's
        gradient reduction (same shapes/dtypes/schedule — overlap.reduce_flush
        over zeros) is compiled once and re-timed every ~50 steps; its wall
        time is reported as the step's reduce phase and subtracted from
        compute so phases still sum to the measured step time."""
        if self._dp_size is None or self._dp_size <= 1:
            return None
        if self._step_i - self._probe_step < self._REDUCE_PROBE_EVERY:
            return self._reduce_s  # cached (or throttled after a failure)
        try:
            if self._reduce_probe is None:
                from jax.sharding import PartitionSpec as _P

                from ..distributed import overlap as _overlap

                axis, mode = self._dp_axis, self._overlap_mode()
                bucket_bytes = self._bucket_bytes

                def reduce_only(*g_vals):
                    return tuple(_overlap.reduce_flush(
                        list(g_vals), axis, bucket_bytes, mode=mode))

                n = len(self.params)
                self._reduce_probe = jax.jit(jax.shard_map(
                    reduce_only, mesh=self._mesh,
                    in_specs=(_P(),) * n, out_specs=(_P(),) * n,
                    axis_names=frozenset({axis}), check_vma=False))
                self._probe_zeros = [jnp.zeros_like(np.asarray(p._value))
                                     for p in self.params]
                # warm call so the timed one below never measures a compile
                jax.block_until_ready(self._reduce_probe(*self._probe_zeros))
            t0 = time.perf_counter()
            jax.block_until_ready(self._reduce_probe(*self._probe_zeros))
            self._reduce_s = time.perf_counter() - t0
            self._probe_step = self._step_i
        except Exception:  # the probe must never take down training
            self._reduce_probe = None
            self._reduce_s = None
            self._probe_step = self._step_i  # throttles the retry
        return self._reduce_s

    def _emit_step(self, loss, gnorm, lr_f, t0, batch_vals):
        """Build and stage this step's telemetry record (telemetry path only).
        Reading loss/gnorm to host scalars is the step's natural sync point,
        so compute_s measured after it covers the device work."""
        try:
            loss_f = float(loss)
            gnorm_f = float(gnorm) if gnorm is not None else None
        except (TypeError, ValueError):
            loss_f = gnorm_f = None
        compute_s = time.perf_counter() - t0
        if self._n_params is None:
            self._n_params = int(sum(
                int(np.prod(p._value.shape)) for p in self.params))
        if self._batch_dims is None:
            # batch shapes are static per compiled signature; scan once
            samples = tokens = None
            for leaf in jax.tree_util.tree_leaves(batch_vals):
                shape = tuple(getattr(leaf, "shape", ()))
                if not shape:
                    continue
                if samples is None:
                    samples = int(shape[0])
                if tokens is None and len(shape) >= 2 and \
                        jnp.issubdtype(getattr(leaf, "dtype", jnp.float32),
                                       jnp.integer):
                    tokens = int(shape[0]) * int(shape[1])
                if samples is not None and tokens is not None:
                    break
            self._batch_dims = (samples, tokens)
        samples, tokens = self._batch_dims
        core = {
            "step": self._step_i - 1,
            "loss": loss_f,
            "grad_norm": gnorm_f,
            "lr": lr_f,
            "compute_s": compute_s,
            "skipped": self.last_skipped if self._nan_guard else False,
            # on the fused single-program path the all-reduce overlaps the
            # backward inside XLA; no host-observable reduce wait exists —
            # reduce_s below is the PROBED comm cost attributed out of
            # compute_s, not a wait the host saw
            "reduce_overlapped": True,
        }
        reduce_s = self._probe_reduce_s()
        if reduce_s:
            core["reduce_s"] = round(min(reduce_s, compute_s), 6)
        if samples:
            core["samples"] = samples
        if tokens:
            core["tokens"] = tokens
            core["flops"] = 6.0 * self._n_params * tokens
        try:
            from ..core import autotune as _autotune
            from . import compile_cache as _cc

            core["autotune"] = _autotune.stats_snapshot()
            core["compile_cache"] = dict(_cc.cache_info())
        except Exception:
            pass
        _telemetry.get_telemetry().on_step(core)
        if self._nan_guard and self.last_skipped:
            _flight.on_nan_skip(self._step_i - 1, loss=loss_f)

    def sync_to_optimizer(self):
        """Push compiled-state back so optimizer.state_dict() reflects
        training. COPIES are handed over: the live self.opt_state buffers
        are donated to the next compiled step, and the optimizer must not
        hold soon-to-be-invalidated arrays."""
        copied = jax.tree_util.tree_map(
            lambda x: x.copy() if hasattr(x, "copy") else x, self.opt_state)
        self.optimizer.sync_state_from(self.params, copied)

    def lower(self, *batch):
        batch_vals = _tensor_leaves(batch)
        param_vals = [p._value for p in self.params]
        buffer_vals = [b._value for b in self.buffers]
        lr = jnp.asarray(0.0, jnp.float32)
        seed = jnp.asarray(0, jnp.int32)
        return self._jitted.lower(param_vals, buffer_vals, self.opt_state, lr, seed, batch_vals)
