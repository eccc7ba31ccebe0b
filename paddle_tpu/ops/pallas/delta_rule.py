"""The gated delta rule with a decay per key channel (Kimi Delta Attention,
the linear-attention layers of GLM-5.3-Flash), as two Pallas TPU kernels and
their XLA forms.

A head keeps a state S [K, V] in float32. Token t, with its key k_t and query
q_t (L2-normalised, [K]), value v_t [V], write strength beta_t in (0, 1) and
log-decay g_t [K] (every channel in (lower bound, 0)):

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t

A token that does not exist (a row that pads a prefill chunk) is given
beta = 0 and g = 0 and leaves the state as it was.

Prefill (`kda_chunk`, `delta_rule_chunked`): the chunk-wise (WY) form over
sub-chunks of SUB = 16 tokens. Within a sub-chunk, with G the inclusive
cumulative sum of g from the sub-chunk's start,

    A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])     (j < t)
    B_tj = sum_c q_t[c] k_j[c] exp(G_t[c] - G_j[c])     (j <= t)
    T    = (I + diag(beta) A)^-1                         unit lower triangular
    U    = T (beta v - (beta k exp G) S_0)               the new values' part
    O    = (q exp G) S_0 + B U
    S_16 = diag(exp G_16) S_0 + (k exp(G_16 - G))^T U

A and B are products of two factors, each taken against the sub-chunk's
middle row (exp(G_t - G_r) and exp(G_r - G_j)), and T is the product
(I - N)(I + N^2)(I + N^4)(I + N^8) of N = diag(beta) A, nilpotent of order
16: no division. Only U's second term, O's first and the state's update read
the state; the rest does not.

`kda_chunk` takes a grid step of BT = 128 tokens (8 sub-chunks) a head in
two phases.
1. What does not read the state, the whole block at once, as full-width
   products: A and B one product of (beta k; q) exp(G - G_r), stacked
   [256, K], against k exp(G_r - G), each sub-chunk factored at its own
   middle row and the result masked to the 16 x 16 diagonal blocks; T from
   the block-diagonal N, whose powers stay block-diagonal (six [128, 128]
   products); W = T (beta k exp G) and U_0 = T beta v one product each;
   each sub-chunk's exp(G_16) as a column [K, 1]. The blocks the mask drops
   pair one sub-chunk's factor with another's. With g above the gate's
   lower bound of -5 a factor is at most e^40 (8 tokens of -5), so an entry
   is at most 128 e^75 and never leaves float32's range.
2. The state's own products, one sub-chunk after the other: [W_c;
   (q exp G)_c] S as one [32, K] x S product, u_c = U_0,c - W_c S, and
   S <- diag(exp G_16) S + (k exp(G_16 - G))_c^T u_c. After the last,
   O = (q exp G) S + B U is one product of the block-diagonal B.

Decode (`kda_decode`, `delta_rule_step`): one token a slot, the state read
and written once (4 MiB a slot a layer at 64 heads of 128 x 128).

Kernel shapes. `kda_chunk`: a grid over (sequences x head blocks, token
blocks of BT = 128), HB heads a step whose sub-chunk chains are independent
and interleave; the state of each head in VMEM scratch across the token
blocks, read at the first and written at the last. `kda_decode`: a grid over
(slots, head blocks of 8); a block's q, k, beta k and g are turned once so
that a head's values lie down the sublanes, and the update is vector work
on [128, 128] tiles; the state's block is updated in place
(input_output_aliases). Every product of both is float32 at precision
highest, and the state is float32: the state is the sum of up to a whole
context of rank-one writes, and a bf16 product's rounding would compound
through it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 16            # tokens of a sub-chunk of the chunk-wise form
BT = 128            # tokens of a grid step of kda_chunk
HB_CHUNK = 4        # heads of a grid step of kda_chunk
HB_DECODE = 8       # heads of a grid step of kda_decode
HP = lax.Precision.HIGHEST
F32 = jnp.float32


def _unit_lower_inverse(n, eye):
    """(I + N)^-1 for N nilpotent of order SUB (a power of two): strictly
    lower triangular of size SUB, or block-diagonal in such blocks.
    (I - N)(I + N^2)(I + N^4)..., ending where a power of N is zero."""
    dot = functools.partial(jnp.matmul, precision=HP)
    p = eye - n
    m = dot(n, n)
    size = 2
    while size < SUB:
        p = p + dot(p, m)
        m = dot(m, m)
        size *= 2
    return p


# -------------------------------------------------------------- XLA forms
def delta_rule_step_xla(q, k, v, g, beta, state, scale):
    """One token a row. q, k, g [b, H, K]; v [b, H, V]; beta [b, H]; state
    [b, H, K, V] float32. Returns (o [b, H, V] float32, new state)."""
    s = state * jnp.exp(g.astype(F32))[..., None]
    kf = k.astype(F32)
    kv = jnp.einsum("bhk,bhkv->bhv", kf, s, precision=HP)
    u = beta.astype(F32)[..., None] * (v.astype(F32) - kv)
    s = s + kf[..., None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q.astype(F32), s, precision=HP)
    return o * scale, s


def _sub_chunks(x, n):
    """[b, T, H, D] -> [b, H, n, SUB, D] float32."""
    b, t, h = x.shape[:3]
    return x.astype(F32).reshape(b, n, SUB, h, -1).transpose(0, 3, 1, 2, 4)


def delta_rule_chunked_xla(q, k, v, g, beta, state, scale):
    """The chunk-wise form over a sequence. q, k, g [b, T, H, K]; v [b, T, H,
    V]; beta [b, T, H]; state [b, H, K, V] float32; T a multiple of SUB.
    Returns (o [b, T, H, V] float32, the state after the last token)."""
    b, t, h, _ = q.shape
    n = t // SUB
    q, k, v, g = (_sub_chunks(x, n) for x in (q, k, v, g))
    beta = _sub_chunks(beta[..., None], n)                   # [b, H, n, S, 1]
    G = jnp.cumsum(g, axis=3)
    mid = G[:, :, :, SUB // 2:SUB // 2 + 1]
    eq, ek, eg = jnp.exp(G - mid), jnp.exp(mid - G), jnp.exp(G)
    kb = k * beta
    row = jnp.arange(SUB)[:, None]
    col = jnp.arange(SUB)[None, :]
    kk = k * ek
    a = jnp.einsum("bhnsk,bhnjk->bhnsj", kb * eq, kk, precision=HP)
    a = jnp.where(row > col, a, 0.0)
    bm = jnp.einsum("bhnsk,bhnjk->bhnsj", q * eq, kk, precision=HP)
    bm = jnp.where(row >= col, bm, 0.0)
    tinv = _unit_lower_inverse(a, jnp.eye(SUB, dtype=F32))
    w = jnp.matmul(tinv, kb * eg, precision=HP)             # [b, H, n, S, K]
    u0 = jnp.matmul(tinv, v * beta, precision=HP)           # [b, H, n, S, V]
    qg = q * eg
    last = G[:, :, :, -1:]
    kd = k * jnp.exp(last - G)
    gl = jnp.exp(last[:, :, :, 0])                          # [b, H, n, K]

    def step(s, xs):
        w, u0, qg, kd, bm, gl = xs
        u = u0 - jnp.matmul(w, s, precision=HP)
        o = jnp.matmul(qg, s, precision=HP) + jnp.matmul(bm, u, precision=HP)
        s = gl[..., None] * s + jnp.einsum("bhsk,bhsv->bhkv", kd, u,
                                            precision=HP)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, u0, qg, kd, bm, gl))
    state, o = lax.scan(step, state.astype(F32), xs)        # o [n, b, H, S, V]
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, t, h, -1)
    return o * scale, state


# ---------------------------------------------------------------- kernels
def _chunk_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref,
                  o_ref, s_out_ref, s_ref, wq_ref, u_ref, kd_ref, b_ref,
                  gl_ref, *, heads, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _load():
        s_ref[...] = s0_ref[0]

    n = BT // SUB
    row = lax.broadcasted_iota(jnp.int32, (BT, BT), 0)
    col = lax.broadcasted_iota(jnp.int32, (BT, BT), 1)
    same = row // SUB == col // SUB              # the diagonal blocks
    eye = (row == col).astype(F32)
    dot = functools.partial(jnp.dot, precision=HP, preferred_element_type=F32)
    nt = functools.partial(lax.dot_general,
                           dimension_numbers=(((1,), (1,)), ((), ())),
                           precision=HP, preferred_element_type=F32)
    tn = functools.partial(lax.dot_general,
                           dimension_numbers=(((0,), (0,)), ((), ())),
                           precision=HP, preferred_element_type=F32)

    def each(h, i):      # row i of every sub-chunk of head h's G: [n, K]
        return g_ref[0, h, pl.ds(i, n, stride=SUB), :]

    def down(x):         # [n, K] -> [BT, K]: each row down its sub-chunk
        return jnp.broadcast_to(x[:, None], (n, SUB, x.shape[-1])
                                ).reshape(BT, -1)

    # 1. what does not read the state, the whole block at once
    for h in range(heads):
        q, k, kb, G = q_ref[0, h], k_ref[0, h], kb_ref[0, h], g_ref[0, h]
        mid, last = down(each(h, SUB // 2)), each(h, SUB - 1)
        eq = jnp.exp(G - mid)
        ab = nt(jnp.concatenate([kb * eq, q * eq]), k * jnp.exp(mid - G))
        a = jnp.where(same & (row > col), ab[:BT], 0.0)
        b_ref[h] = jnp.where(same & (row >= col), ab[BT:], 0.0)
        tinv = _unit_lower_inverse(a, eye)
        eg = jnp.exp(G)
        u_ref[h] = dot(tinv, vb_ref[0, h])                          # U_0
        wq_ref[h] = jnp.concatenate(
            [dot(tinv, kb * eg).reshape(n, SUB, -1),                # W
             (q * eg).reshape(n, SUB, -1)], axis=1)
        kd_ref[h] = k * jnp.exp(down(last) - G)
        gl_ref[h] = jnp.exp(last).T

    # 2. the state's own products, one sub-chunk after the other
    for c in range(n):
        r = pl.ds(c * SUB, SUB)
        for h in range(heads):       # independent chains, interleaved
            s = s_ref[h]
            x = dot(wq_ref[h, c], s)              # W_c S over (q e^G)_c S
            u = u_ref[h, r] - x[:SUB]
            u_ref[h, r] = u
            o_ref[0, h, r, :] = x[SUB:]
            s_ref[h] = s * gl_ref[h, :, c:c + 1] + tn(kd_ref[h, r], u)

    # 3. the outputs' own part: O = (q e^G) S + B U, B block-diagonal
    for h in range(heads):
        o_ref[0, h] = (o_ref[0, h] + dot(b_ref[h], u_ref[h])) * scale

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        s_out_ref[0] = s_ref[...]


def kda_chunk_supports(t: int, h: int, dk: int, dv: int) -> bool:
    return t % BT == 0 and h % HB_CHUNK == 0 and dk % 128 == 0 \
        and dv % 128 == 0


def kda_chunk(q, k, v, g, beta, state, *, scale, interpret=False):
    """The chunk-wise form as one kernel (see the module docstring): inputs
    as delta_rule_chunked_xla's, T a multiple of BT."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    to_heads = lambda x: x.astype(F32).transpose(0, 2, 1, 3)   # noqa: E731
    beta = beta.astype(F32)[..., None]
    G = jnp.cumsum(g.astype(F32).reshape(b, t // SUB, SUB, h, dk), axis=2)
    qh, kh = to_heads(q), to_heads(k)
    kb, vb = to_heads(k * beta), to_heads(v * beta)
    gh = to_heads(G.reshape(b, t, h, dk))
    tok = lambda d: pl.BlockSpec((1, HB_CHUNK, BT, d),          # noqa: E731
                                 lambda i, j: (i // (h // HB_CHUNK),
                                               i % (h // HB_CHUNK), j, 0))
    st = pl.BlockSpec((1, HB_CHUNK, dk, dv),
                      lambda i, j: (i // (h // HB_CHUNK),
                                    i % (h // HB_CHUNK), 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=HB_CHUNK, scale=scale),
        grid=(b * (h // HB_CHUNK), t // BT),
        in_specs=[tok(dk), tok(dk), tok(dk), tok(dv), tok(dk), st],
        out_specs=[tok(dv), st],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), F32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), F32)],
        scratch_shapes=[
            pltpu.VMEM((HB_CHUNK, dk, dv), F32),                    # S
            pltpu.VMEM((HB_CHUNK, BT // SUB, 2 * SUB, dk), F32),    # W; q e^G
            pltpu.VMEM((HB_CHUNK, BT, dv), F32),                    # U_0, U
            pltpu.VMEM((HB_CHUNK, BT, dk), F32),                    # kd
            pltpu.VMEM((HB_CHUNK, BT, BT), F32),                    # B
            pltpu.VMEM((HB_CHUNK, dk, BT // SUB), F32)],            # e^G_16
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="kda_chunk",
        interpret=interpret,
    )(qh, kh, kb, vb, gh, state.astype(F32))
    return o.transpose(0, 2, 1, 3), s


def _decode_kernel(q_ref, k_ref, kb_ref, v_ref, g_ref, s_ref, o_ref,
                   s_out_ref, *, heads, scale):
    qt = q_ref[0].T                                  # [K, heads]
    kt = k_ref[0].T
    kbt = kb_ref[0].T
    et = jnp.exp(g_ref[0]).T
    v = v_ref[0]                                     # [heads, V]
    for h in range(heads):
        s = s_ref[0, h] * et[:, h:h + 1]
        kv = jnp.sum(s * kt[:, h:h + 1], axis=0, keepdims=True)
        s = s + kbt[:, h:h + 1] * (v[h:h + 1, :] - kv)
        s_out_ref[0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * qt[:, h:h + 1], axis=0,
                                       keepdims=True) * scale


def kda_decode_supports(h: int, dk: int, dv: int) -> bool:
    return h % HB_DECODE == 0 and dk % 128 == 0 and dv % 128 == 0


def kda_decode(q, k, v, g, beta, state, *, scale, interpret=False):
    """One token a slot as one kernel: inputs as delta_rule_step_xla's; the
    state is updated in place."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    f = lambda x: x.astype(F32)                      # noqa: E731
    kb = f(k) * f(beta)[..., None]
    vec = lambda d: pl.BlockSpec((1, HB_DECODE, d),  # noqa: E731
                                 lambda i, j: (i, j, 0))
    st = pl.BlockSpec((1, HB_DECODE, dk, dv), lambda i, j: (i, j, 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_decode_kernel, heads=HB_DECODE, scale=scale),
        grid=(b, h // HB_DECODE),
        in_specs=[vec(dk), vec(dk), vec(dk), vec(dv), vec(dk), st],
        out_specs=[vec(dv), st],
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), F32),
                   jax.ShapeDtypeStruct((b, h, dk, dv), F32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="kda_decode",
        interpret=interpret,
    )(f(q), f(k), kb, f(v), f(g), state)
    return o, s


# --------------------------------------------------------------- dispatch
def delta_rule_chunked(q, k, v, g, beta, state, scale):
    """The kernel on the TPU (and in interpret mode) where the shapes fit
    it, the XLA form elsewhere; T a multiple of SUB (BT for the kernel)."""
    from . import interpret_mode, pallas_enabled

    b, t, h, dk = q.shape
    if pallas_enabled() and kda_chunk_supports(t, h, dk, v.shape[-1]):
        return kda_chunk(q, k, v, g, beta, state, scale=scale,
                         interpret=interpret_mode())
    return delta_rule_chunked_xla(q, k, v, g, beta, state, scale)


def delta_rule_step(q, k, v, g, beta, state, scale):
    from . import interpret_mode, pallas_enabled

    b, h, dk = q.shape
    if pallas_enabled() and kda_decode_supports(h, dk, v.shape[-1]):
        return kda_decode(q, k, v, g, beta, state, scale=scale,
                          interpret=interpret_mode())
    return delta_rule_step_xla(q, k, v, g, beta, state, scale)
