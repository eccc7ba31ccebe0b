"""chip_smoke.py off the chip, the compile cache's placement, and
the places that used to fall back in silence (Place, peak FLOP/s, analyzer).

The chip itself is reached only through the chip tool; what can be held
here is the control flow: no TPU -> nonzero exit and no ok line, and the
smoke's own correctness check must be able to fail.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(*argv, env=None, cwd=ROOT):
    """Run python off the chip, with nothing placing the compile cache."""
    e = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.pop("FLAGS_jit_compile_cache_dir", None)
    e.update(env or {})
    return subprocess.run([sys.executable, *argv], env=e, cwd=cwd,
                          capture_output=True, text=True, timeout=600)


# ------------------------------------------------------------ no TPU, no ok
@pytest.mark.parametrize("args", [(), ("--chips", "4")],
                         ids=["one_chip", "four_chips"])
def test_chip_smoke_fails_without_a_tpu(args):
    res = _python(os.path.join(ROOT, "chip_smoke.py"), *args)
    assert res.returncode != 0
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"       # names what it found
    assert "tpu" in last["error"].lower()
    assert '"ok": true' not in res.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program: nothing to import, nothing measured."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    res = _python(str(tmp_path / "chip_smoke.py"), "--rehearse",
                  cwd=tmp_path, env={"PYTHONPATH": ""})
    assert res.returncode != 0
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "paddle_tpu" in last["error"]


def test_smoke_reference_check_can_fail():
    """chip_smoke's agreement check passes on model.generate's own tokens
    and refuses one wrong token — a check that cannot fail checks nothing."""
    sys.path.insert(0, ROOT)
    import chip_smoke
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny())
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(0, 1023, 12)] for _ in range(2)]
    out = np.asarray(model.generate(
        paddle.to_tensor(np.asarray(prompts, np.int32)),
        max_new_tokens=6)._value)[:, 12:]
    rows = [(f"r{i}", p, [int(t) for t in o])
            for i, (p, o) in enumerate(zip(prompts, out))]
    chip_smoke._check_against_reference(model, rows, 6, {})
    wrong = list(rows[1][2])
    wrong[3] = (wrong[3] + 1) % 1023
    with pytest.raises(AssertionError, match="r1"):
        chip_smoke._check_against_reference(
            model, [rows[0], ("r1", rows[1][1], wrong)], 6, {})


# ------------------------------------------------------- compile cache place
_CACHE_PROBE = """
import sys
from paddle_tpu.jit import compile_cache
import jax
d = compile_cache.enable_persistent_cache(*sys.argv[1:])
print(d); print(jax.config.jax_compilation_cache_dir)
"""


def _cache_dirs(*args, env=None):
    res = _python("-c", _CACHE_PROBE, *args, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    return res.stdout.strip().splitlines()[-2:]


def test_cache_honours_jax_compilation_cache_dir(tmp_path):
    placed = str(tmp_path / "placed")
    # the environment wins over an explicit argument: no other directory
    # is set in code
    for args in [(), (str(tmp_path / "other"),)]:
        returned, configured = _cache_dirs(
            *args, env={"JAX_COMPILATION_CACHE_DIR": placed})
        assert returned == configured == placed
    assert not (tmp_path / "other").exists()


def test_cache_default_is_one_fixed_path_in_the_checkout():
    first = _cache_dirs()
    second = _cache_dirs()          # another process, same path
    assert first == second
    assert first[0] == first[1] == os.path.join(ROOT, ".jax_cache")


# ------------------------------------------------------ no silent fallbacks
def test_tpu_place_raises_without_a_tpu():
    with pytest.raises(RuntimeError, match="tpu"):
        paddle.TPUPlace(0).jax_device()
    assert paddle.CPUPlace().jax_device().platform == "cpu"


def test_peak_flops_is_a_table_not_a_default(monkeypatch):
    from paddle_tpu.observability import telemetry

    monkeypatch.setenv("BENCH_PEAK_FLOPS", "1")      # no longer read
    assert telemetry.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no published peak"):
        telemetry.peak_flops()                       # the CPU has no MFU


def test_crashed_rule_fails_the_lint(monkeypatch):
    """A rule that raises is a dead analyzer, not a clean program."""
    import dataclasses

    import jax.numpy as jnp

    from paddle_tpu import analysis
    from paddle_tpu.analysis import registry

    def boom(program):
        raise AttributeError("jax moved it")

    monkeypatch.setitem(
        registry._RULES, "host-sync",
        dataclasses.replace(analysis.get_rule("host-sync"), check=boom))
    with pytest.raises(AttributeError, match="jax moved it"):
        analysis.analyze(lambda x: x + 1, jnp.ones(3), rules=["host-sync"])
