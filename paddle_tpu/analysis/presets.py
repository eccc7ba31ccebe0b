"""Lintable model-zoo presets for the CLI (`python -m paddle_tpu.analysis`).

Each preset builds a tiny-config model-zoo model + optimizer + TrainStep and
returns lint targets: (label, thunk -> Report). Everything here is
trace-only — no device execution — so linting the zoo takes seconds under
JAX_PLATFORMS=cpu. These presets are the negative corpus: the acceptance
bar is ZERO findings on all of them (tests/test_static_analysis.py).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

from .analyzer import analyze, lint_train_step
from .findings import Report

LintTarget = Tuple[str, Callable[[], Report]]


def _ids(batch=2, seq=16, vocab=1024):
    return np.random.RandomState(0).randint(
        0, vocab, (batch, seq)).astype(np.int32)


def _train_step(model, loss_fn):
    import paddle_tpu as paddle
    from ..jit.trainer import TrainStep

    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    return TrainStep(model, loss_fn, opt)


def _causal_lm_targets(name, model) -> List[LintTarget]:
    import paddle_tpu as paddle

    ids = _ids()

    def fwd(ids_arr):
        t = paddle.Tensor(ids_arr)
        return model(t, labels=t)

    def lint_fwd():
        return analyze(fwd, ids, target=f"{name}.forward")

    def lint_step():
        step = _train_step(
            model, lambda b: model(b, labels=b))
        return lint_train_step(step, (paddle.to_tensor(ids),),
                               target=f"TrainStep({name})")

    return [(f"{name}.forward", lint_fwd), (f"{name}.train_step", lint_step)]


def _gpt_targets() -> List[LintTarget]:
    from ..models import GPTConfig, GPTForCausalLM

    return _causal_lm_targets("gpt-tiny", GPTForCausalLM(GPTConfig.tiny()))


def _llama_targets() -> List[LintTarget]:
    from ..models import LlamaConfig, LlamaForCausalLM

    return _causal_lm_targets(
        "llama-tiny", LlamaForCausalLM(LlamaConfig.tiny()))


def _bert_targets() -> List[LintTarget]:
    import paddle_tpu as paddle
    from ..models import BertConfig, BertForSequenceClassification

    model = BertForSequenceClassification(BertConfig.tiny())
    ids = _ids()
    labels = np.zeros((ids.shape[0],), np.int32)
    ce = paddle.nn.CrossEntropyLoss()

    def fwd(ids_arr):
        return model(paddle.Tensor(ids_arr))

    def lint_fwd():
        return analyze(fwd, ids, target="bert-tiny.forward")

    def lint_step():
        step = _train_step(model, lambda b, y: ce(model(b), y))
        return lint_train_step(
            step, (paddle.to_tensor(ids), paddle.to_tensor(labels)),
            target="TrainStep(bert-tiny)")

    return [("bert-tiny.forward", lint_fwd),
            ("bert-tiny.train_step", lint_step)]


def _pallas_targets() -> List[LintTarget]:
    """Trace the repo's own Pallas kernels at TPU-representative shapes —
    the pallas-tiling rule inspects the pallas_call eqns (no TPU needed)."""
    import jax.numpy as jnp

    from ..ops.pallas.flash_attention import flash_attention
    from ..ops.pallas.fused_norm import fused_rms_norm

    q = np.zeros((2, 256, 4, 128), np.float32)  # [b, s, h, d]

    def lint_flash():
        return analyze(
            lambda q_, k_, v_: flash_attention(
                jnp.asarray(q_), jnp.asarray(k_), jnp.asarray(v_)),
            q, q, q, target="pallas.flash_attention")

    x = np.zeros((256, 512), np.float32)
    w = np.zeros((512,), np.float32)

    def lint_norm():
        return analyze(
            lambda x_, w_: fused_rms_norm(jnp.asarray(x_), jnp.asarray(w_)),
            x, w, target="pallas.rms_norm")

    return [("pallas.flash_attention", lint_flash),
            ("pallas.rms_norm", lint_norm)]


PRESETS: Dict[str, Callable[[], List[LintTarget]]] = {
    "gpt": _gpt_targets,
    "llama": _llama_targets,
    "bert": _bert_targets,
    "pallas": _pallas_targets,
}


def lint_presets(names=None) -> List[Tuple[str, Report]]:
    """Build + lint the requested presets; returns (label, Report) rows."""
    names = list(names or PRESETS)
    out: List[Tuple[str, Report]] = []
    for name in names:
        for label, thunk in PRESETS[name]():
            out.append((label, thunk()))
    return out
