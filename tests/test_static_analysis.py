"""Static analyzer tests (ISSUE r8): positive AND negative case per rule,
model-zoo e2e cleanliness, FLAGS_jit_lint trainer integration, CLI smoke.

Everything here is trace-only (jax.make_jaxpr) — runs under the CPU conftest
backend with no device execution beyond what the trainer tests compile.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import LintError, Severity, analyze


def _hits(report, rule):
    return [f for f in report.findings if f.rule == rule]


# --------------------------------------------------------------------------
# rule 1: collective-axis
# --------------------------------------------------------------------------

def test_collective_axis_positive_unbound():
    r = analyze(lambda x: jax.lax.psum(x, "bogus"), np.ones((4,), np.float32))
    hits = _hits(r, "collective-axis")
    assert hits and hits[0].severity == Severity.ERROR
    assert "bogus" in hits[0].message


def test_collective_axis_degenerate_size_one():
    r = analyze(lambda x: jax.lax.psum(x, "dp"), np.ones((4,), np.float32),
                axis_env=[("dp", 1)])
    hits = _hits(r, "collective-axis")
    assert hits and hits[0].severity == Severity.WARNING  # no-op collective


def test_collective_axis_negative():
    r = analyze(lambda x: jax.lax.psum(x, "dp"), np.ones((4,), np.float32),
                axis_env=[("dp", 8)])
    assert not _hits(r, "collective-axis")


def test_collective_axis_positive_partial_ppermute():
    # a perm that is no bijection over the axis: the devices it leaves out
    # receive zeros, a wrong result in silence
    r = analyze(lambda x: jax.lax.ppermute(x, "dp", [(0, 1)]),
                np.ones((4,), np.float32), axis_env={"dp": 8})
    assert _hits(r, "collective-axis")


def test_collective_axis_negative_ring_all_reduce():
    # a decomposed ring all-reduce is 2*(world-1) full-cycle ppermutes over
    # a bound axis: real communication, neither no-op nor zero-fill
    from paddle_tpu.distributed import overlap

    r = analyze(lambda x: overlap.ring_all_reduce(x, "dp", world=8),
                np.ones((64,), np.float32), axis_env={"dp": 8})
    assert not _hits(r, "collective-axis")


# --------------------------------------------------------------------------
# rule 2: dtype-promotion
# --------------------------------------------------------------------------

def test_dtype_promotion_positive_f64_host_arg():
    r = analyze(lambda x: jnp.sum(x), np.ones((4,), np.float64))
    assert _hits(r, "dtype-promotion")


def test_dtype_promotion_positive_bf16_accumulation():
    a = np.ones((16, 16), np.float32)
    with jax.enable_x64(False):
        r = analyze(lambda x: x.astype(jnp.bfloat16) @ x.astype(jnp.bfloat16),
                    a)
    hits = _hits(r, "dtype-promotion")
    assert hits and any("accumul" in f.message for f in hits)


def test_dtype_promotion_negative():
    r = analyze(lambda x: x @ x, np.ones((16, 16), np.float32))
    assert not _hits(r, "dtype-promotion")


def _many_f64_args(k):
    # k host-side float64 leaves -> k independent dtype-promotion findings
    args = tuple(np.ones((2,), np.float64) for _ in range(k))
    return analyze(lambda *xs: sum(jnp.sum(x) for x in xs), *args)


@pytest.fixture
def _dtype_cap():
    from paddle_tpu.core.flags import get_flag, set_flags

    old = get_flag("lint_dtype_max_reports")

    def put(v):
        set_flags({"lint_dtype_max_reports": v})

    yield put
    set_flags({"lint_dtype_max_reports": old})


def test_dtype_promotion_cap_emits_suppression_summary(_dtype_cap):
    _dtype_cap(3)
    r = _many_f64_args(6)
    hits = _hits(r, "dtype-promotion")
    warns = [f for f in hits if f.severity == Severity.WARNING]
    infos = [f for f in hits if f.severity == Severity.INFO]
    assert len(warns) == 3
    assert len(infos) == 1 and "suppressed" in infos[0].message
    assert "3" in infos[0].message  # 6 candidates - 3 reported


def test_dtype_promotion_cap_zero_is_unlimited(_dtype_cap):
    _dtype_cap(0)
    r = _many_f64_args(12)
    hits = _hits(r, "dtype-promotion")
    assert len(hits) >= 12  # every arg reported (x64 off may add eqn hits)
    assert not any("suppressed" in f.message for f in hits)


def test_dtype_promotion_default_cap_unchanged():
    r = _many_f64_args(12)  # default cap is 8
    hits = _hits(r, "dtype-promotion")
    warns = [f for f in hits if f.severity == Severity.WARNING]
    assert len(warns) == 8
    assert any("suppressed" in f.message for f in hits)


# --------------------------------------------------------------------------
# rule 3: recompile-hazard
# --------------------------------------------------------------------------

def test_recompile_positive_weak_scalar():
    r = analyze(lambda s, x: x * s, 3.0, np.ones((4,), np.float32))
    hits = _hits(r, "recompile-hazard")
    assert hits and "weak" in hits[0].message


def test_recompile_positive_nonhashable_static():
    r = analyze(lambda x: x + 1, np.ones((4,), np.float32),
                static_args={"cfg": [1, 2, 3]})
    hits = _hits(r, "recompile-hazard")
    assert hits and hits[0].severity == Severity.ERROR


def test_recompile_negative():
    r = analyze(lambda s, x: x * s, np.float32(3.0),
                np.ones((4,), np.float32))
    assert not _hits(r, "recompile-hazard")


# --------------------------------------------------------------------------
# rule 4: donation
# --------------------------------------------------------------------------

def test_donation_positive_unused_donated():
    r = analyze(lambda a, b: jnp.sum(b),
                np.ones((8,), np.float32), np.ones((8,), np.float32),
                donate_argnums=(0,))
    hits = _hits(r, "donation")
    assert hits and "donat" in hits[0].message


def test_donation_negative_in_place_update():
    r = analyze(lambda a: a + 1.0, np.ones((8,), np.float32),
                donate_argnums=(0,))
    assert not _hits(r, "donation")


# --------------------------------------------------------------------------
# rule 5: dead-output
# --------------------------------------------------------------------------

def test_dead_output_positive():
    def bad(x, w):
        _ = x @ w
        return jnp.sum(x)

    r = analyze(bad, np.ones((4, 4), np.float32), np.ones((4, 4), np.float32))
    hits = _hits(r, "dead-output")
    assert hits and hits[0].primitive == "dot_general"


def test_dead_output_negative():
    def good(x, w):
        y = x @ w
        return jnp.sum(x) + jnp.sum(y)

    r = analyze(good, np.ones((4, 4), np.float32), np.ones((4, 4), np.float32))
    assert not _hits(r, "dead-output")


def test_dead_output_ignores_engine_vjp_residue():
    """Grad-enabled eager traces carry cheap dead vjp residuals from the
    dispatch-time jax.vjp engine — those must NOT be reported."""
    m = paddle.nn.Linear(4, 4)

    def fwd(x):
        return paddle.nn.functional.gelu(m(paddle.Tensor(x)))

    r = analyze(fwd, np.ones((2, 4), np.float32))
    assert not _hits(r, "dead-output")


# --------------------------------------------------------------------------
# rule 6: host-sync
# --------------------------------------------------------------------------

def test_host_sync_positive():
    def bad(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    r = analyze(bad, np.ones((4,), np.float32))
    assert _hits(r, "host-sync")


def test_host_sync_negative():
    r = analyze(lambda x: x + 1, np.ones((4,), np.float32))
    assert not _hits(r, "host-sync")


# --------------------------------------------------------------------------
# rule 7: pallas-tiling
# --------------------------------------------------------------------------

def _pallas_program(block):
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def fn(x):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct(block, jnp.float32),
            grid=(1,),
            in_specs=[pl.BlockSpec(block, lambda i: (0, 0))],
            out_specs=pl.BlockSpec(block, lambda i: (0, 0)),
        )(x)

    return fn


def test_pallas_tiling_positive_lane_misaligned():
    r = analyze(_pallas_program((128, 100)), np.ones((128, 200), np.float32))
    hits = _hits(r, "pallas-tiling")
    assert hits and any("128" in f.message for f in hits)


def test_pallas_tiling_negative_aligned():
    r = analyze(_pallas_program((128, 128)), np.ones((128, 128), np.float32))
    assert not _hits(r, "pallas-tiling")


def test_pallas_tiling_vmem_overflow():
    # 2 x (4096*4096*4B) double-buffered = 256 MiB >> 16 MiB VMEM
    r = analyze(_pallas_program((4096, 4096)),
                np.ones((4096, 4096), np.float32))
    hits = _hits(r, "pallas-tiling")
    assert hits and any(f.severity == Severity.ERROR and "VMEM" in f.message
                        for f in hits)


# --------------------------------------------------------------------------
# rule 8: prefetch-effects
# --------------------------------------------------------------------------

def test_prefetch_effects_positive():
    def bad(x):
        jax.debug.print("step={x}", x=x)
        return x * 2

    r = analyze(bad, np.ones((4,), np.float32),
                context={"prefetch_active": True})
    hits = _hits(r, "prefetch-effects")
    assert hits and "prefetch" in hits[0].message


def test_prefetch_effects_negative_pure():
    r = analyze(lambda x: x * 2, np.ones((4,), np.float32),
                context={"prefetch_active": True})
    assert not _hits(r, "prefetch-effects")


def test_prefetch_effects_negative_collective_not_flagged():
    # NamedAxisEffect from a mesh-bound collective is a tracing artifact,
    # not a host-visible side effect
    r = analyze(lambda x: jax.lax.psum(x, "dp"), np.ones((4,), np.float32),
                axis_env=[("dp", 8)], context={"prefetch_active": True})
    assert not _hits(r, "prefetch-effects")


# --------------------------------------------------------------------------
# e2e: model zoo lints clean
# --------------------------------------------------------------------------

@pytest.mark.parametrize("preset", ["gpt", "llama", "bert"])
def test_model_preset_is_clean(preset):
    from paddle_tpu.analysis.presets import lint_presets

    for label, report in lint_presets([preset]):
        assert not report.findings, f"{label}: {report}"


# --------------------------------------------------------------------------
# trainer integration: FLAGS_jit_lint + dp_axis errors
# --------------------------------------------------------------------------

def _tiny_step(loss_hook=None, **kw):
    from paddle_tpu.jit.trainer import TrainStep

    paddle.seed(0)
    model = paddle.nn.Linear(4, 2)
    mse = paddle.nn.MSELoss()

    def loss_fn(x, y):
        out = model(x)
        if loss_hook is not None:
            loss_hook(out)
        return mse(out, y)

    opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
    step = TrainStep(model, loss_fn, opt, **kw)
    batch = (paddle.to_tensor(np.ones((4, 4), np.float32)),
             paddle.to_tensor(np.ones((4, 2), np.float32)))
    return step, batch


def test_jit_lint_warn_mode_emits_warning():
    from paddle_tpu.core.flags import set_flags

    def hook(out):
        jax.debug.print("out={o}", o=out._value)

    step, batch = _tiny_step(loss_hook=hook)
    set_flags({"jit_lint": "warn"})
    try:
        with pytest.warns(UserWarning, match="host-sync"):
            step(*batch)
    finally:
        set_flags({"jit_lint": "off"})


def test_jit_lint_raise_mode_fails_fast_on_error():
    from paddle_tpu.analysis.findings import Finding
    from paddle_tpu.analysis.registry import _RULES, register_rule
    from paddle_tpu.core.flags import set_flags

    @register_rule("test-always-error", "test", Severity.ERROR)
    def _always(program):
        yield Finding(rule="test-always-error", severity=Severity.ERROR,
                      message="synthetic ERROR for raise-mode test")

    step, batch = _tiny_step()
    set_flags({"jit_lint": "raise"})
    try:
        with pytest.raises(LintError, match="test-always-error"):
            step(*batch)
    finally:
        set_flags({"jit_lint": "off"})
        _RULES.pop("test-always-error", None)
    # the step object stays usable once the flag is off
    step(*batch)


def test_jit_lint_off_by_default_and_clean_step_passes():
    from paddle_tpu.core.flags import get_flag, set_flags

    assert str(get_flag("jit_lint")) == "off"
    step, batch = _tiny_step()
    set_flags({"jit_lint": "raise"})
    try:
        step(*batch)  # clean program: no LintError, no crash
    finally:
        set_flags({"jit_lint": "off"})


def test_dp_axis_missing_mesh_is_clear_error():
    from paddle_tpu.distributed import mesh as dmesh

    old = dmesh.get_mesh()
    dmesh.set_mesh(None)
    try:
        with pytest.raises(ValueError, match="active mesh"):
            _tiny_step(dp_axis="dp")
    finally:
        dmesh.set_mesh(old)


def test_dp_axis_wrong_name_lists_available_axes():
    from paddle_tpu.distributed import mesh as dmesh

    old = dmesh.get_mesh()
    dmesh.set_mesh(dmesh.build_mesh(dp=8))
    try:
        with pytest.raises(ValueError, match="available axes"):
            _tiny_step(dp_axis="nope")
    finally:
        dmesh.set_mesh(old)


def test_dp_batch_not_divisible_is_clear_error():
    from paddle_tpu.distributed import mesh as dmesh

    old = dmesh.get_mesh()
    dmesh.set_mesh(dmesh.build_mesh(dp=8))
    try:
        step, _ = _tiny_step(dp_axis="dp")
        bad = (paddle.to_tensor(np.ones((6, 4), np.float32)),
               paddle.to_tensor(np.ones((6, 2), np.float32)))
        with pytest.raises(ValueError, match="not divisible"):
            step(*bad)
    finally:
        dmesh.set_mesh(old)


# --------------------------------------------------------------------------
# CLI smoke
# --------------------------------------------------------------------------

def test_cli_list_rules(capsys):
    from paddle_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("collective-axis", "dtype-promotion", "recompile-hazard",
                "donation", "dead-output", "host-sync", "pallas-tiling",
                "prefetch-effects"):
        assert rid in out


def test_cli_rejects_unknown_preset():
    from paddle_tpu.analysis.__main__ import main

    with pytest.raises(SystemExit):
        main(["no-such-preset"])


def test_cli_pallas_preset_clean(capsys):
    from paddle_tpu.analysis.__main__ import main

    assert main(["pallas"]) == 0
    assert "0 finding(s)" in capsys.readouterr().out
