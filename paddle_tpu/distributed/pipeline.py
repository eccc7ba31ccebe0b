"""Pipeline-parallel schedule engines (pure jnp level).

Reference: 1F1B host schedule `forward_backward_pipeline`
(python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:382) and
the cached-shape p2p layer (fleet/meta_parallel/pp_utils/p2p_communication.py).

TPU-native redesign (NOT a port): the reference drives 1F1B from the host with
NCCL p2p between per-stage processes. Here the whole schedule is ONE compiled
SPMD program over the 'pp' mesh axis:

  * each pp rank holds its stage's parameters (stage-stacked arrays, leading
    dim S sharded over 'pp');
  * stage handoff is `lax.ppermute` over ICI neighbors (the send/recv);
  * the 1F1B tick loop is a `lax.scan` whose body does one forward substep and
    one 1F1B backward substep per tick, with ring buffers for in-flight
    activations (max S in flight per rank — the 1F1B memory property);
  * the backward recomputes the stage forward from its saved input (the
    reference couples PP with recompute the same way), so in-flight state is
    activations at stage boundaries only;
  * bubbles are masked compute, exactly like the reference's idle ticks.

Schedule arithmetic (stage s in [0,S), microbatch m in [0,M)):
  forward tick  t_f(s,m) = m + s                     (warmup, m < S - s)
                t_f(s,m) = 2m + s - 1                (steady state)
  backward tick t_b(s,m) = 2m + 2(S-1) - s
Derived properties used below: t_f(s+1,m) >= t_f(s,m)+1 (activations buffer at
most S ticks), t_b(s-1,m) = t_b(s,m)+1 (grad handoff is a pure rotation), and
steady-state ticks alternate fwd/bwd per rank (the "1F1B" in the name).

Two engines with one signature:
  pipeline_1f1b(...)    manual-vjp 1F1B (above)
  pipeline_fthenb(...)  forward scan + jax AD backward (GPipe / "F-then-B",
                        reference analog pipeline_scheduler_pass.py FThenB),
                        with jax.checkpoint on the stage so memory also stays
                        at stage boundaries.

Plus the interleaved virtual-stage engine (reference
PipelineParallelWithInterleave, pipeline_parallel.py:814, schedule :959):
  pipeline_interleave(...)  each pp rank hosts V "virtual" chunks; global
                        stage g = v*S + r lives on rank r = g mod S. Every
                        handoff — within-chunk r->r+1 AND chunk-boundary
                        wraparound (S-1)->0 — is the SAME ring ppermute, so
                        the whole schedule stays one uniform SPMD program.
                        The per-substep schedule (derivation in the
                        pipeline_interleave docstring) fills the pipeline in
                        O(D) substeps of 1/V-size stages, cutting the bubble
                        by V vs plain 1F1B — the reason interleave exists.
                        It also supports heterogeneous first/last ends
                        (pre_fn/post_fn with a SHARED param tree), which is
                        how tied embedding+head across pipeline stages
                        (reference pp_layers.py shared_comm) is expressed:
                        the shared weights are replicated over 'pp' and their
                        grad is psum'ed over the axis — the reference's
                        first/last-stage grad all-reduce.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _zeros_like_tree(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _tree_where(pred, a, b):
    return jax.tree_util.tree_map(lambda x, y: jnp.where(pred, x, y), a, b)


def _squeeze0(tree):
    return jax.tree_util.tree_map(lambda x: jnp.squeeze(x, 0), tree)


def _expand0(tree):
    return jax.tree_util.tree_map(lambda x: jnp.expand_dims(x, 0), tree)


def _rank_shard_map(body, mesh, n, axis, in_specs, out_specs):
    """shard_map over `axis` handing `body` its stage id as the FIRST arg:
    partial-manual over `axis` (other mesh axes stay under GSPMD) with
    lax.axis_index for the id."""
    wrapped = lambda *a: body(lax.axis_index(axis), *a)
    return jax.shard_map(
        wrapped, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset({axis}), check_vma=False)


def pipeline_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh: Mesh,
    n_stages: int,
    stage_params: Any,
    loss_params: Any,
    xs: jax.Array,
    labels: jax.Array,
    axis: str = "pp",
):
    """Run the 1F1B schedule; returns (loss, d_stage_params, d_loss_params, d_xs).

    stage_fn(params, x) -> y        with y.shape == x.shape (homogeneous stages)
    loss_fn(loss_params, y, label) -> scalar mean loss for one microbatch
    stage_params: pytree with leading dim S (sharded over `axis`)
    xs, labels:   leading dim M = number of microbatches (replicated over `axis`)
    """
    S, M = n_stages, xs.shape[0]
    T = 2 * M + 2 * S - 3  # last tick: t_b(0, M-1) = 2(M-1) + 2(S-1)
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i + 1, i) for i in range(S - 1)]

    def body(sid, stage_params_l, loss_params_l, xs_l, labels_l):
        params = _squeeze0(stage_params_l)  # local stage's params
        is_first = sid == 0
        is_last = sid == S - 1

        mb_shape = xs_l.shape[1:]
        ring = jnp.zeros((S,) + mb_shape, xs_l.dtype)  # in-flight stage inputs
        gbuf = jnp.zeros(mb_shape, xs_l.dtype)         # rotating upstream grad
        gparams0 = _zeros_like_tree(params)
        gloss0 = _zeros_like_tree(loss_params_l)
        gxs0 = jnp.zeros_like(xs_l)
        loss0 = jnp.zeros((), jnp.float32)

        def warmup_of(s):
            return S - s  # W_s: microbatches forwarded before first backward

        def fwd_index(t, s):
            """Microbatch index of the forward substep of stage s at tick t
            (and its validity)."""
            m_warm = t - s
            in_warm = (m_warm >= 0) & (m_warm < jnp.minimum(warmup_of(s), M))
            num = t + 1 - s
            m_steady = num // 2
            in_steady = (num % 2 == 0) & (m_steady >= warmup_of(s)) & (m_steady < M)
            m = jnp.where(in_warm, m_warm, m_steady)
            return m, in_warm | in_steady

        def bwd_index(t, s):
            num = t - 2 * (S - 1) + s
            m = num // 2
            valid = (num >= 0) & (num % 2 == 0) & (m < M)
            return m, valid

        def tick(carry, t):
            ring, gbuf, gparams, gloss, gxs, loss_acc = carry

            # ---- forward substep -------------------------------------------
            m_f, f_valid = fwd_index(t, sid)
            m_f = jnp.clip(m_f, 0, M - 1)
            x_f = jnp.where(is_first, xs_l[m_f], ring[m_f % S])
            y = stage_fn(params, x_f)
            y_send = jnp.where(f_valid, y, jnp.zeros_like(y))

            # ---- backward substep (recompute-from-input, 1F1B order) -------
            m_b, b_valid = bwd_index(t, sid)
            m_b = jnp.clip(m_b, 0, M - 1)
            x_b = jnp.where(is_first, xs_l[m_b], ring[m_b % S])
            y_b, stage_vjp = jax.vjp(stage_fn, params, x_b)
            lval, loss_vjp = jax.vjp(loss_fn, loss_params_l, y_b, labels_l[m_b])
            glp, gy_loss, _ = loss_vjp(jnp.ones_like(lval) / M)
            gy = jnp.where(is_last, gy_loss.astype(gbuf.dtype), gbuf)
            gp, gx = stage_vjp(gy.astype(y_b.dtype))

            bmask = b_valid
            gparams = _tree_add(gparams, _tree_where(bmask, gp, _zeros_like_tree(gp)))
            gloss = _tree_add(
                gloss, _tree_where(bmask & is_last, glp, _zeros_like_tree(glp)))
            gxs = gxs.at[m_b].add(
                jnp.where(bmask & is_first, gx.astype(gxs.dtype), jnp.zeros_like(gx, gxs.dtype)))
            loss_acc = loss_acc + jnp.where(
                bmask & is_last, lval.astype(jnp.float32) / M, 0.0)
            gx_send = jnp.where(bmask, gx, jnp.zeros_like(gx))

            # ---- communications (the reference's p2p send/recv layer) ------
            y_rot = lax.ppermute(y_send, axis, fwd_perm)
            gbuf = lax.ppermute(gx_send, axis, bwd_perm)

            # arrival: what my upstream neighbor forwarded this tick
            m_in, in_valid = fwd_index(t, sid - 1)
            m_in = jnp.clip(m_in, 0, M - 1)
            in_valid = in_valid & (sid >= 1)
            slot = m_in % S
            ring = ring.at[slot].set(jnp.where(in_valid, y_rot, ring[slot]))

            return (ring, gbuf, gparams, gloss, gxs, loss_acc), None

        carry0 = (ring, gbuf, gparams0, gloss0, gxs0, loss0)
        (ring, gbuf, gparams, gloss, gxs, loss_acc), _ = lax.scan(
            tick, carry0, jnp.arange(T))

        # only one rank holds each piece; make outputs axis-invariant
        loss_out = lax.psum(loss_acc, axis)
        gloss_out = jax.tree_util.tree_map(lambda g: lax.psum(g, axis), gloss)
        gxs_out = lax.psum(gxs, axis)
        return _expand0(gparams), gloss_out, gxs_out, loss_out

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        jax.tree_util.tree_map(lambda _: P(), loss_params),
        P(),
        P(),
    )
    out_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        jax.tree_util.tree_map(lambda _: P(), loss_params),
        P(),
        P(),
    )
    fn = _rank_shard_map(body, mesh, n_stages, axis, in_specs, out_specs)
    d_stage, d_loss_p, d_xs, loss = fn(stage_params, loss_params, xs, labels)
    return loss, d_stage, d_loss_p, d_xs


def pipeline_fthenb(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh: Mesh,
    n_stages: int,
    stage_params: Any,
    loss_params: Any,
    xs: jax.Array,
    labels: jax.Array,
    axis: str = "pp",
):
    """F-then-B engine: forward rotation scan, backward generated by jax AD
    (the transpose of ppermute/scan IS the reverse schedule). Stage is
    jax.checkpoint'ed so only stage-boundary activations are stored."""
    S, M = n_stages, xs.shape[0]
    T = M + S - 1
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    stage_ckpt = jax.checkpoint(stage_fn)

    def forward(sid, stage_params_l, loss_params_l, xs_l, labels_l):
        params = _squeeze0(stage_params_l)
        is_first = sid == 0
        is_last = sid == S - 1
        mb_shape = xs_l.shape[1:]

        def tick(state, t):
            m_in = jnp.clip(t, 0, M - 1)
            x = jnp.where(is_first & (t < M), xs_l[m_in], state)
            y = stage_ckpt(params, x)
            m_out = t - (S - 1)
            collect = is_last & (m_out >= 0)
            lval = loss_fn(loss_params_l, y, labels_l[jnp.clip(m_out, 0, M - 1)])
            contrib = jnp.where(collect, lval.astype(jnp.float32) / M, 0.0)
            state = lax.ppermute(y, axis, fwd_perm)
            return state, contrib

        state0 = jnp.zeros(mb_shape, xs_l.dtype)
        _, contribs = lax.scan(tick, state0, jnp.arange(T))
        return lax.psum(jnp.sum(contribs), axis)

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        jax.tree_util.tree_map(lambda _: P(), loss_params),
        P(),
        P(),
    )
    fn = _rank_shard_map(forward, mesh, n_stages, axis, in_specs, P())

    def total(sp, lp, x):
        return fn(sp, lp, x, labels)

    loss, grads = jax.value_and_grad(total, argnums=(0, 1, 2))(
        stage_params, loss_params, xs)
    d_stage, d_loss_p, d_xs = grads
    return loss, d_stage, d_loss_p, d_xs


def pipeline_interleave(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh: Mesh,
    n_stages: int,
    stage_params: Any,
    loss_params: Any,
    xs: jax.Array,
    labels: jax.Array,
    axis: str = "pp",
    n_virtual: int = 1,
    pre_fn: Callable | None = None,
    post_fn: Callable | None = None,
    shared_params: Any = None,
):
    """Interleaved virtual-stage schedule as one compiled SPMD program.

    Layout: D = S*V global stages; global stage g = v*S + r runs on rank
    r = g % S as its chunk v = g // S. `stage_params` leaves have leading dim
    D ordered as index i = r*V + v, so sharding P('pp') on dim 0 hands rank r
    exactly its V chunks.

    Schedule (all in substep "ticks"; each tick every rank runs ONE masked
    forward substep and ONE masked backward substep of a 1/V-size stage):

      t_f(g, m) = (m % S) + S*V*(m // S) + g
      t_b(g, m) = t_f(g, m) + 2*(D - 1 - g) + 1

    Properties (each is a proof obligation the code relies on):
      * t_f(g,m) = t_f(g-1,m) + 1 and t_b(g,m) = t_b(g+1,m) + 1 — every
        activation/grad is consumed exactly one tick after it is produced,
        so handoffs need NO buffering: the ppermute arrival IS the operand.
      * per rank per tick at most one forward and one backward slot fire
        (proof: mod-S then div-V decomposition of t is injective in (v, m)),
        and in steady state both fire -> full utilization.
      * fill = O(D) ticks of u/V-cost substeps -> bubble ~ 2*D*(u/V) = 2*S*u
        independent of V in ticks but 1/V in cost per tick relative to plain
        1F1B's full-size stages; total span T = M*V + D + S - 1 ticks when
        S | M (see code for the exact any-M count).
      * a stage input is needed again at its backward, 2(D-1-g)+1 < 2D ticks
        later; consecutive microbatches hitting the same (rank, chunk) slot
        modulo 2S are exactly 2D ticks apart -> a [V, 2S] ring of stage
        inputs is collision-free.

    pre_fn(shared, raw_x) -> h runs fused into stage 0's substeps;
    post_fn(shared, y) -> logits runs fused into the loss at stage D-1. Both
    read the SAME `shared_params` tree (replicated over 'pp'); its gradient
    collects contributions from both ends and is psum'ed over the axis.

    Returns (loss, d_stage_params, d_shared, d_loss_params, d_xs).
    """
    S, V = n_stages, n_virtual
    D = S * V
    M = xs.shape[0]
    # last tick: t_b(0, M-1) = t_f(0, M-1) + 2(D-1) + 1, exact for any M
    T = ((M - 1) % S) + S * V * ((M - 1) // S) + 2 * D
    ring_fwd = [(i, (i + 1) % S) for i in range(S)]
    ring_bwd = [(i, (i - 1) % S) for i in range(S)]
    has_pre = pre_fn is not None
    has_post = post_fn is not None
    if shared_params is None:
        shared_params = ()

    # hidden (pipeline-carried) microbatch shape/dtype
    if has_pre:
        h_aval = jax.eval_shape(pre_fn, shared_params, jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype))
    else:
        h_aval = jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype)

    def body(r, sp_l, sh_l, lp_l, xs_l, labels_l):

        def fwd_slot(t):
            q = t - r
            b = q % S
            p = q // S
            v = p % V
            m = (p // V) * S + b
            return v, m, (q >= 0) & (m >= 0) & (m < M)

        def bwd_slot(t):
            q = t - D - (S - 1 - r)
            b = q % S
            p = q // S
            v = (V - 1) - (p % V)
            m = (p // V) * S + b
            return v, m, (q >= 0) & (m >= 0) & (m < M)

        def pick(tree, v):
            return jax.tree_util.tree_map(lambda a: a[v], tree)

        h0 = jnp.zeros(h_aval.shape, h_aval.dtype)
        xbuf0 = jnp.zeros((V, 2 * S) + h_aval.shape, h_aval.dtype)
        gparams0 = _zeros_like_tree(sp_l)
        gshared0 = _zeros_like_tree(sh_l)
        gloss0 = _zeros_like_tree(lp_l)
        gxs0 = jnp.zeros_like(xs_l)

        def tick(carry, t):
            h_recv, g_recv, xbuf, gparams, gshared, gloss, gxs, loss_acc = carry

            # ---- forward substep -------------------------------------------
            v_f, m_f, fvalid = fwd_slot(t)
            g_f = v_f * S + r
            m_fc = jnp.clip(m_f, 0, M - 1)
            params_f = pick(sp_l, v_f)
            if has_pre:
                h_in = lax.cond(
                    g_f == 0,
                    lambda: pre_fn(sh_l, xs_l[m_fc]).astype(h_aval.dtype),
                    lambda: h_recv,
                )
            else:
                h_in = jnp.where(g_f == 0, xs_l[m_fc], h_recv)
            y = stage_fn(params_f, h_in)
            y_send = jnp.where(fvalid & (g_f < D - 1), y, jnp.zeros_like(y))
            slot_f = m_fc % (2 * S)
            xbuf = xbuf.at[v_f, slot_f].set(
                jnp.where(fvalid, h_in, xbuf[v_f, slot_f]))

            # ---- backward substep (recompute-from-input) -------------------
            v_b, m_b, bvalid = bwd_slot(t)
            g_b = v_b * S + r
            m_bc = jnp.clip(m_b, 0, M - 1)
            params_b = pick(sp_l, v_b)
            xh = xbuf[v_b, m_bc % (2 * S)]
            is_first_g = g_b == 0
            is_last_g = g_b == D - 1
            lab = labels_l[m_bc]
            raw = xs_l[m_bc]

            def full(pv, sp, lp, x_hidden):
                if has_pre:
                    h = lax.cond(
                        is_first_g,
                        lambda: pre_fn(sp, raw).astype(h_aval.dtype),
                        lambda: x_hidden,
                    )
                else:
                    h = x_hidden
                yy = stage_fn(pv, h)
                if has_post:
                    lval = lax.cond(
                        is_last_g,
                        lambda: loss_fn(lp, post_fn(sp, yy), lab).astype(jnp.float32),
                        lambda: jnp.zeros((), jnp.float32),
                    )
                else:
                    lval = lax.cond(
                        is_last_g,
                        lambda: loss_fn(lp, yy, lab).astype(jnp.float32),
                        lambda: jnp.zeros((), jnp.float32),
                    )
                return yy, lval

            (y_b, lval), vjp = jax.vjp(full, params_b, sh_l, lp_l, xh)
            gy = jnp.where(is_last_g | ~bvalid, jnp.zeros_like(g_recv), g_recv)
            ct_loss = jnp.where(bvalid, 1.0 / M, 0.0).astype(jnp.float32)
            gpv, gsh, glp, gxh = vjp((gy.astype(y_b.dtype), ct_loss))

            gparams = jax.tree_util.tree_map(
                lambda acc, g: acc.at[v_b].add(jnp.where(bvalid, g, jnp.zeros_like(g))),
                gparams, gpv)
            gshared = _tree_add(
                gshared, _tree_where(bvalid, gsh, _zeros_like_tree(gsh)))
            gloss = _tree_add(
                gloss, _tree_where(bvalid, glp, _zeros_like_tree(glp)))
            if not has_pre:
                gxs = gxs.at[m_bc].add(jnp.where(
                    bvalid & is_first_g, gxh.astype(gxs.dtype),
                    jnp.zeros_like(gxh, gxs.dtype)))
            loss_acc = loss_acc + jnp.where(bvalid, lval, 0.0) / M
            gx_send = jnp.where(bvalid & (g_b > 0), gxh, jnp.zeros_like(gxh))

            # ---- ring handoffs ---------------------------------------------
            h_recv = lax.ppermute(y_send, axis, ring_fwd)
            g_recv = lax.ppermute(gx_send.astype(h_aval.dtype), axis, ring_bwd)
            return (h_recv, g_recv, xbuf, gparams, gshared, gloss, gxs,
                    loss_acc), None

        carry0 = (h0, h0, xbuf0, gparams0, gshared0, gloss0, gxs0,
                  jnp.zeros((), jnp.float32))
        carry, _ = lax.scan(tick, carry0, jnp.arange(T))
        _, _, _, gparams, gshared, gloss, gxs, loss_acc = carry

        loss_out = lax.psum(loss_acc, axis)
        gshared_out = jax.tree_util.tree_map(lambda g: lax.psum(g, axis), gshared)
        gloss_out = jax.tree_util.tree_map(lambda g: lax.psum(g, axis), gloss)
        gxs_out = lax.psum(gxs, axis)
        return gparams, gshared_out, gloss_out, gxs_out, loss_out

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        jax.tree_util.tree_map(lambda _: P(), shared_params),
        jax.tree_util.tree_map(lambda _: P(), loss_params),
        P(),
        P(),
    )
    out_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        jax.tree_util.tree_map(lambda _: P(), shared_params),
        jax.tree_util.tree_map(lambda _: P(), loss_params),
        P(),
        P(),
    )
    fn = _rank_shard_map(body, mesh, S, axis, in_specs, out_specs)
    d_stage, d_shared, d_loss_p, d_xs, loss = fn(
        stage_params, shared_params, loss_params, xs, labels)
    return loss, d_stage, d_shared, d_loss_p, d_xs


ENGINES = {"1F1B": pipeline_1f1b, "FThenB": pipeline_fthenb,
           "Interleave": pipeline_interleave}
