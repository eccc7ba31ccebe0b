"""Context-parallel (ring attention / Ulysses) tests on the 8-device CPU mesh.

No reference test exists for these (the reference lacks context parallelism,
SURVEY.md §5.7); correctness oracle = dense single-device attention on the
full sequence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from paddle_tpu.distributed.context_parallel import (
    all_gather_seq,
    reduce_scatter_seq,
    ring_attention,
    scatter_seq,
    ulysses_attention,
)

B, S, H, D = 2, 64, 8, 16
N = 4  # ring size


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("sep",))


def _qkv(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    return mk(), mk(), mk()


def _dense(q, k, v, causal):
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        m = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention(causal):
    q, k, v = _qkv()
    mesh = _mesh()
    spec = P(None, "sep", None, None)

    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sep", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out = jax.jit(fn)(q, k, v)
    ref = _dense(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention(causal):
    q, k, v = _qkv(1)
    mesh = _mesh()
    spec = P(None, "sep", None, None)

    fn = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sep", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out = jax.jit(fn)(q, k, v)
    ref = _dense(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=1e-3)


def test_ring_attention_grads():
    q, k, v = _qkv(2)
    mesh = _mesh()
    spec = P(None, "sep", None, None)

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sep", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    g1 = jax.grad(lambda q: (ring(q, k, v) ** 2).sum())(q)
    g2 = jax.grad(lambda q: (_dense(q, k, v, True) ** 2).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=5e-3, rtol=1e-2)


class TestRingFlash:
    """Ring attention routed through the Pallas flash chunk kernel
    (flash_attention_with_lse) — VERDICT r3 item 3. Oracle: dense full-seq
    attention AND the dense-chunk ring path (flags off)."""

    @pytest.fixture(autouse=True)
    def _flash_flags(self):
        # enable flash+interpret for the test, restoring PRIOR values after
        # (hardcoding False would disable the flash path for the rest of the
        # session on a TPU run)
        from paddle_tpu.core import flags

        saved = {k: flags.get_flag(k)
                 for k in ("use_flash_attention", "pallas_interpret")}
        flags.set_flags({"use_flash_attention": True,
                         "pallas_interpret": True})
        yield
        flags.set_flags(saved)

    def _flags(self, on):
        from paddle_tpu.core import flags

        flags.set_flags({"use_flash_attention": on, "pallas_interpret": on})

    def _ring(self, causal):
        # check_vma=False like the production wrapper (_sp_attention_fn):
        # the pallas interpreter can't thread vma through its internal mul
        mesh = _mesh()
        spec = P(None, "sep", None, None)
        return jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sep", causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity(self, causal):
        q, k, v = _qkv(3)
        from paddle_tpu.distributed.context_parallel import (
            _flash_chunk_supported,
        )

        assert _flash_chunk_supported(S // N, D)  # flash path is taken
        out = jax.jit(self._ring(causal))(q, k, v)
        ref = _dense(q, k, v, causal)
        np.testing.assert_allclose(out, ref, atol=2e-4, rtol=1e-3)

    def test_grad_parity_vs_dense_ring(self):
        q, k, v = _qkv(4)

        def loss(fn, q, k, v):
            return (fn(q, k, v) ** 2).sum()

        gq_f, gk_f, gv_f = jax.grad(
            lambda q, k, v: loss(self._ring(True), q, k, v),
            argnums=(0, 1, 2))(q, k, v)
        self._flags(False)  # dense-chunk reference ring (fixture restores)
        gq_d, gk_d, gv_d = jax.grad(
            lambda q, k, v: loss(self._ring(True), q, k, v),
            argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(gq_f, gq_d, atol=5e-3, rtol=1e-2)
        np.testing.assert_allclose(gk_f, gk_d, atol=5e-3, rtol=1e-2)
        np.testing.assert_allclose(gv_f, gv_d, atol=5e-3, rtol=1e-2)


def test_sp_utils_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, S, 32)), jnp.float32)
    mesh = _mesh()
    shard = P(None, "sep", None)
    rep = P(None, None, None)

    # all_gather(shard) == identity on the full array
    gat = shard_map(
        lambda x: all_gather_seq(x, "sep"),
        mesh=mesh, in_specs=(shard,), out_specs=rep, check_vma=False,
    )
    np.testing.assert_allclose(gat(x), x, atol=1e-6)

    # scatter(full) == shard
    sc = shard_map(
        lambda x: scatter_seq(x, "sep"),
        mesh=mesh, in_specs=(rep,), out_specs=shard, check_vma=False,
    )
    np.testing.assert_allclose(sc(x), x, atol=1e-6)

    # reduce_scatter(replicated) == N * shard
    rs = shard_map(
        lambda x: reduce_scatter_seq(x, "sep"),
        mesh=mesh, in_specs=(rep,), out_specs=shard, check_vma=False,
    )
    np.testing.assert_allclose(rs(x), N * x, atol=1e-5)


# --- CP wired into the model/training path ----------------------------------
class TestSequenceParallelModel:
    """VERDICT r2 #5: context parallelism must be a usable parallelism mode,
    not a library function — a GPT config flag routes attention over 'sep',
    composing with TrainStep. Parity: sep=2 vs sep=1 give the same loss and
    gradients."""

    def _build(self, sp):
        import paddle_tpu as paddle
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(11)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, max_position_embeddings=32,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                        sequence_parallel=sp, use_rotary=True)
        return GPTForCausalLM(cfg)

    def _loss_and_grads(self, model, ids):
        import numpy as np

        loss = model(ids, labels=ids)
        loss.backward()
        gs = {i: np.asarray(p.grad._value)
              for i, p in enumerate(model.parameters()) if p.grad is not None}
        return float(loss.item()), gs

    def test_loss_parity_sep2_vs_sep1(self):
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.distributed as dist

        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 128, (2, 16)).astype(np.int32))

        ref_model = self._build(None)
        ref_loss, ref_gs = self._loss_and_grads(ref_model, ids)

        mesh = dist.build_mesh(sep=2)
        dist.set_mesh(mesh)
        try:
            for mode in ("ring", "ulysses"):
                model = self._build(mode)
                loss, gs = self._loss_and_grads(model, ids)
                assert abs(loss - ref_loss) < 1e-4, (mode, loss, ref_loss)
                assert set(gs) == set(ref_gs)
                for k in gs:
                    np.testing.assert_allclose(gs[k], ref_gs[k], rtol=1e-3,
                                               atol=1e-5, err_msg=f"{mode}:{k}")
        finally:
            dist.set_mesh(None)

    def test_train_step_with_sep_axis(self):
        """Full compiled TrainStep over a dp x sep mesh."""
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.distributed as dist
        from paddle_tpu import nn, optimizer
        from paddle_tpu.distributed.sharding_utils import (
            shard_batch, shard_model_parameters)
        from paddle_tpu.jit.trainer import TrainStep

        mesh = dist.build_mesh(dp=2, sep=2, mp=2)
        dist.set_mesh(mesh)
        try:
            model = self._build("ring")
            shard_model_parameters(model, mesh)
            opt = optimizer.AdamW(1e-4, parameters=model.parameters(),
                                  grad_clip=nn.ClipGradByGlobalNorm(1.0))
            step = TrainStep(model, lambda ids: model(ids, labels=ids), opt)
            ids = paddle.to_tensor(np.random.RandomState(1).randint(
                0, 128, (4, 16)).astype(np.int32))
            shard_batch(ids, mesh, axes=("dp",))
            l0 = float(step(ids).item())
            l1 = float(step(ids).item())
            assert np.isfinite(l0) and np.isfinite(l1)
            assert l1 < l0  # it optimizes
        finally:
            dist.set_mesh(None)
