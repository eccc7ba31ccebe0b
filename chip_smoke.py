"""Does the system still start on the chip? The quickest proof.

    python chip_smoke.py              one TPU chip: phases train, serve, kernels
    python chip_smoke.py --chips 4    four chips: ONLY the sharded train step
                                      and the one-device step it is compared with
    python chip_smoke.py --rehearse   the same control flow at a tiny size on
                                      whatever jax finds, Pallas kernels in
                                      interpret mode where there is no TPU.
                                      Finds wrong paths and arguments at no chip
                                      time; never reports ok.

Each phase drives a main path through the entry points a user calls, at the
full width of a model the repo supports, with seeded random weights:

  train    GPTForCausalLM at GPT-3 Medium (355M: hidden 1024, 24 layers, 16
           heads, vocab 50304), batch 8, sequence 1024, AdamW, amp bf16,
           through jit.TrainStep: a few steps on one fixed batch.
  serve    GPTConfig.gpt3_1p3b() (hidden 2048, 24 layers, head_dim 128, 2048
           positions) in bf16 through ServingEngine + ServingServer: HTTP
           /generate requests of mixed lengths (chunked prefill, batched
           prefill, prefix-cache partial and full hits, one streamed), then one
           request through a spec_k > 0 engine (multi-query verify kernel).
           Every answer is held to model.generate and to a teacher-forced
           forward of the same model with flash attention off (the plain
           reference).
  kernels  the Pallas kernels the two paths above do not execute (flash at
           head_dim 128 fwd+bwd, fused RMSNorm fwd+bwd at hidden 4096, fused
           RoPE, flat fused AdamW) against jax.numpy references.

A phase fails on an exception, a non-finite value, a missed tolerance, or —
on the chip — a program that should hold a Pallas kernel and does not. The
script exits nonzero, with "ok": false in its last line, if any phase failed
or if jax found no TPU. The last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One process holds the chip: the phases run in sequence in this process and
each frees its arrays before the next. Nothing here starts a child process.
Any time it prints is information, not a benchmark result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import statistics
import sys
import threading
import time
import traceback
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# Logit units. Random bf16 weights give logits of std ~0.9 over a 50k
# vocabulary, so top-two margins under ~0.1 are common, and two correct
# implementations (XLA cached attention vs the paged kernel) differ by a few
# 1e-2 after 24 bf16 layers. A wrong kernel picks tokens several units down.
LOGIT_TOL = 0.15
# first loss of a freshly initialised LM against ln(vocab)
FIRST_LOSS_BAND = 0.5
# Sharded steps vs the same steps on one device, relative. The second loss
# is the sensitive one: it is computed from the first step's update, while
# one AdamW step at lr 1e-4 moves the 355M-parameter fingerprint by ~3e-8 of
# itself whatever the gradients were. Seen on four v5e chips: 5e-6 on the
# first loss (PERF.md, PR 21).
SHARDED_LOSS_RTOL = 1e-3
SHARDED_FINGERPRINT_RTOL = 1e-6

SIZES = {
    "real": dict(
        train=dict(vocab=50304, hidden=1024, layers=24, heads=16, batch=8,
                   seq=1024, steps=6),
        # long = 6 whole KV blocks of 16, so asking it again is a
        # full-prompt cache hit; 3 prefill chunks of 32
        serve=dict(config="gpt3_1p3b", slots=8, short=24, long=96, shared=64,
                   new=32, spec_k=4),
        kernels=dict(b=2, s=1024, h=8, d=128, rows=4096, hidden=4096,
                     n_adamw=4_000_003),
    ),
    "tiny": dict(
        train=dict(vocab=512, hidden=64, layers=2, heads=4, batch=2, seq=128,
                   steps=4),
        serve=dict(config=dict(vocab_size=512, hidden_size=64, num_layers=2,
                               num_heads=4, max_position_embeddings=256),
                   slots=4, short=24, long=96, shared=64, new=12, spec_k=4),
        kernels=dict(b=1, s=128, h=2, d=64, rows=64, hidden=256,
                     n_adamw=70_003),
    ),
}


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def _free():
    import jax

    gc.collect()
    jax.clear_caches()


def _has_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.as_text()


def _require(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------- train
def _gpt_train_step(sz, seed, mesh=None):
    """The 355M model, its optimizer and its TrainStep."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit.trainer import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], num_heads=sz["heads"],
        max_position_embeddings=sz["seq"],
        # the default 0.1 fails the flash gate (dropout_p must be 0)
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    if mesh is not None:
        from paddle_tpu.distributed.sharding_utils import (
            shard_model_parameters)

        shard_model_parameters(model, mesh)
    opt = optimizer.AdamW(1e-4, parameters=model.parameters(),
                          weight_decay=0.01)

    def loss_fn(ids):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return model(ids, labels=ids)

    step = TrainStep(model, loss_fn, opt)
    ids = paddle.to_tensor(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (sz["batch"], sz["seq"])).astype(np.int32))
    if mesh is not None:
        from paddle_tpu.distributed.sharding_utils import shard_batch

        shard_batch(ids, mesh, axes=("dp",))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return model, step, ids, n_params


def phase_train(sz, seed, on_tpu):
    import jax

    model, step, ids, n_params = _gpt_train_step(sz, seed)
    say("train", f"GPT {n_params / 1e6:.1f}M params, batch {sz['batch']} "
                 f"seq {sz['seq']}, AdamW, amp O1 bf16, TrainStep")
    flash = _has_kernel(step.lower(ids))
    say("train", f"flash-attention kernel in the step program: {flash}")
    losses, secs = [], []
    for _ in range(sz["steps"]):
        t0 = time.perf_counter()
        losses.append(float(jax.block_until_ready(step(ids)._value)))
        secs.append(time.perf_counter() - t0)
    say("train", "losses: " + " ".join(f"{x:.4f}" for x in losses))
    say("train", f"compile + first step: {secs[0]:.1f} s; later steps "
                 f"(median of {len(secs) - 1}, around block_until_ready): "
                 f"{statistics.median(secs[1:]) * 1e3:.1f} ms")
    stats = jax.devices()[0].memory_stats() or {}
    say("train", f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    _require(all(math.isfinite(x) for x in losses), "a loss is not finite")
    _require(losses[-1] < losses[0],
             f"loss did not fall: {losses[0]} -> {losses[-1]}")
    _require(abs(losses[0] - math.log(sz["vocab"])) < FIRST_LOSS_BAND,
             f"first loss {losses[0]:.3f} is not within {FIRST_LOSS_BAND} of "
             f"ln(vocab) = {math.log(sz['vocab']):.3f}")
    if on_tpu:
        _require(flash, "the train step holds no flash-attention kernel "
                        "(FLAGS_use_flash_attention is on and the shapes "
                        "pass flash_attention.supports)")


# ------------------------------------------------------------------- serve
def _post(url, body, timeout):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


class _Client(threading.Thread):
    """One HTTP /generate request; streamed ones timestamp every line."""

    def __init__(self, url, name, prompt, new, stream_eos=None):
        super().__init__(name=f"client-{name}", daemon=True)
        self.url, self.label, self.prompt, self.new = url, name, prompt, new
        self.stream_eos = stream_eos
        self.tokens, self.telemetry, self.error = None, None, None
        self.arrivals = []        # seconds since send, per streamed line

    def run(self):
        body = {"prompt": self.prompt, "max_new_tokens": self.new}
        t0 = time.perf_counter()
        try:
            if self.stream_eos is None:
                with _post(self.url + "/generate", body, 900) as r:
                    out = json.loads(r.read())
                self.tokens = out["output_tokens"]
                self.telemetry = out["telemetry"]
            else:
                # an eos id makes the engine fetch tokens every tick; how
                # many lines the handler makes of them is printed
                body.update(stream=True, eos_token_id=self.stream_eos)
                toks = []
                with _post(self.url + "/generate", body, 900) as r:
                    for line in r:
                        o = json.loads(line)
                        if o.get("tokens"):
                            toks += o["tokens"]
                            self.arrivals.append(time.perf_counter() - t0)
                        if o.get("done"):
                            self.telemetry = o.get("telemetry")
                self.tokens = toks
            self.wall_s = time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001 - reported by the phase
            self.error = f"{type(e).__name__}: {e}"


def _run_wave(server, loop_errors, clients):
    for c in clients:
        c.start()
    deadline = time.monotonic() + 900
    while any(c.is_alive() for c in clients):
        _require(server.loop_alive() and not loop_errors,
                 "the engine loop died:\n" + "\n".join(loop_errors))
        _require(time.monotonic() < deadline, "requests did not finish")
        time.sleep(0.05)
    for c in clients:
        _require(c.error is None, f"request {c.label}: {c.error}")
        tel = c.telemetry or {}
        rate = tel.get("decode_tok_s")
        line = (f"request {c.label}: prompt {len(c.prompt)} (cached "
                f"{tel.get('prefix_matched_tokens')}), {len(c.tokens)} new, "
                f"wall {c.wall_s:.2f} s, engine ttft "
                f"{(tel.get('ttft_s') or 0) * 1e3:.0f} ms, mean token gap "
                + (f"{1e3 / rate:.1f} ms" if rate else "n/a"))
        if c.arrivals:
            line += (f"; streamed in {len(c.arrivals)} lines, the first at "
                     f"{c.arrivals[0] * 1e3:.0f} ms")
        say("serve", line)
    return clients


def _check_against_reference(model, rows, new, eos_of):
    """rows: (name, prompt, engine_tokens); eos_of: name -> eos id of the
    requests that had one. Holds every answer to

    (a) model.generate on the same prompt: equal tokens up to the first
        difference, and at that position the two candidates' reference
        logits within LOGIT_TOL of each other (a near-tie, not a fault);
    (b) a teacher-forced full forward of the same model over prompt +
        engine tokens, flash attention off: at EVERY generated position
        (the first comes from prefill, the rest from the paged decode or
        verify kernel) the engine's token scores within LOGIT_TOL of the
        reference's best logit.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor

    # (a) model.generate, batched by prompt length (one program pair each)
    ref = {}
    by_len = {}
    for name, prompt, _ in rows:
        by_len.setdefault(len(prompt), []).append((name, prompt))
    for plen, group in by_len.items():
        out = model.generate(
            paddle.to_tensor(np.asarray([p for _, p in group], np.int32)),
            max_new_tokens=new)
        out = np.asarray(out._value)[:, plen:]
        for (name, _), toks in zip(group, out):
            toks = [int(t) for t in toks]
            if eos_of.get(name) in toks:
                toks = toks[:toks.index(eos_of[name]) + 1]
            ref[name] = toks

    # (b) teacher-forced logits: one jitted full forward over all rows
    width = max(len(p) + len(t) for _, p, t in rows)
    ids = np.zeros((len(rows), width), np.int32)
    for r, (_, p, t) in enumerate(rows):
        ids[r, :len(p) + len(t)] = p + t
    leaves = list(model.parameters()) + list(model.buffers())

    def forward(vals, ids):
        saved = [p._value for p in leaves]
        try:
            for p, v in zip(leaves, vals):
                p._value = v
            lg = model(Tensor(ids))._value.astype(jnp.float32)
        finally:
            for p, v in zip(leaves, saved):
                p._value = v
        # one materialised array for all three outputs: left to fuse, the
        # max and the gather can see different roundings of the same logit
        lg = jax.lax.optimization_barrier(lg)
        nxt = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
        return (jnp.max(lg, -1),
                jnp.take_along_axis(lg, nxt[..., None], -1)[..., 0], lg)

    paddle.set_flags({"use_flash_attention": False})
    try:
        best, chosen, logits = jax.jit(forward)(
            [p._value for p in leaves], jnp.asarray(ids))
    finally:
        paddle.set_flags({"use_flash_attention": True})
    best, chosen = np.asarray(best), np.asarray(chosen)
    _require(np.isfinite(best).all(), "a reference logit is not finite")

    for r, (name, prompt, toks) in enumerate(rows):
        p0 = len(prompt) - 1            # logits at p0 + j choose token j
        deficit = (best - chosen)[r, p0:p0 + len(toks)]
        want = ref[name]
        same = next((j for j, (a, b) in enumerate(zip(toks, want)) if a != b),
                    min(len(toks), len(want)))
        note = ""
        if toks != want:
            _require(same < min(len(toks), len(want)),
                     f"{name}: lengths differ ({len(toks)} vs {len(want)}) "
                     "with equal tokens")
            row = np.asarray(logits[r, p0 + same])
            gap = abs(float(row[toks[same]]) - float(row[want[same]]))
            note = f", first differs at {same} where the two score {gap:.4f} apart"
            _require(gap <= LOGIT_TOL,
                     f"{name}: token {same} is {toks[same]}, model.generate "
                     f"says {want[same]}, and the reference scores them "
                     f"{gap:.3f} apart (> {LOGIT_TOL})")
        say("serve", f"check {name}: {same}/{len(want)} tokens equal to "
                     f"model.generate{note}; worst deficit to the reference's "
                     f"best logit {float(deficit.max()):.4f} "
                     f"(first position {float(deficit[0]):.4f})")
        _require(float(deficit.max()) <= LOGIT_TOL,
                 f"{name}: engine token at generated position "
                 f"{int(deficit.argmax())} scores {float(deficit.max()):.3f} "
                 f"below the reference's best (> {LOGIT_TOL})")


def _serve_over_http(model, sz, prompts, eos):
    """The HTTP waves against one ServingServer. Returns the answered rows
    and whether the decode program holds the paged-attention kernel."""
    import jax

    from paddle_tpu.serving import ServingEngine, ServingServer

    loop_errors = []
    prev_hook = threading.excepthook

    def hook(args):
        loop_errors.append("".join(traceback.format_exception(
            args.exc_type, args.exc_value, args.exc_traceback)))
        prev_hook(args)

    threading.excepthook = hook
    engine = ServingEngine(model, max_slots=sz["slots"])
    server = ServingServer(engine, port=0)       # ephemeral port
    url = server.url()
    rows = []
    try:
        def wave(names, stream=()):
            done = _run_wave(server, loop_errors, [
                _Client(url, n, prompts[n], sz["new"],
                        stream_eos=eos if n in stream else None)
                for n in names])
            rows.extend((c.label, c.prompt, c.tokens) for c in done)

        for tag in ("", "warm_"):
            t0 = time.perf_counter()
            # chunked prefill; its blocks enter the prefix cache
            wave([f"{tag}long_a"])
            # a cached-prefix suffix and three short prompts: one batched
            # prefill; the streamed one carries an eos id
            wave([f"{tag}long_b_shared_prefix", f"{tag}short_a",
                  f"{tag}short_b", f"{tag}short_streamed"],
                 stream=(f"{tag}short_streamed",))
            # the whole prompt is cached: copy-on-write admission
            wave([f"{tag}long_a"])
            rows[-1] = (f"{tag}long_a_again",) + rows[-1][1:]
            say("serve", f"three waves, "
                + ("new prompts of the same shapes (compiles again only "
                   "where a burst lands in different ticks)" if tag
                   else "compiles included")
                + f": {time.perf_counter() - t0:.1f} s")

        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            metrics = r.read().decode()
        stats = engine.stats()
        say("serve", f"/healthz {health['status']}; /metrics "
                     f"{len(metrics.splitlines())} lines; steps "
                     f"{stats['steps']}, prefill programs "
                     f"{stats['prefill_programs']} (batched "
                     f"{stats['batched_prefills']}), prefill tokens "
                     f"{stats['prefill_tokens']}, full-prompt cache hits "
                     f"{stats['cow_admissions']}")
        _require(health["ok"], f"/healthz says {health}")
        _require("serving_" in metrics, "/metrics carries no serving_* metric")
        _require(stats["batched_prefills"] >= 1 and
                 stats["cow_admissions"] >= 2 and
                 stats["prefill_programs"] > stats["batched_prefills"],
                 "a path did not run: batched prefill, chunked prefill and "
                 f"a full-prompt cache hit are all expected ({stats})")
        _, _, pv, bv = engine._functional()
        toks, tables, lens, temps, step_seed = engine._dev
        paged = _has_kernel(engine._decode_jit(False).lower(
            pv, bv, toks, engine.pool.layers, tables, lens, temps, step_seed))
        say("serve", f"paged-attention kernel in the decode program: {paged}")
        mem = jax.devices()[0].memory_stats() or {}
        say("serve", f"bytes_in_use with the engine up: "
                     f"{mem.get('bytes_in_use')} (peak_bytes_in_use "
                     f"{mem.get('peak_bytes_in_use')} is the process's, and "
                     f"the train phase ran first)")
    finally:
        server.stop()
        threading.excepthook = prev_hook
    _require(not loop_errors, "the engine loop raised:\n"
             + "\n".join(loop_errors))
    return rows, paged


def _serve_speculative(model, sz, prompt):
    """One request through the multi-query verify kernel. The drafter
    proposes only when the last two tokens occurred before in the request's
    own history, and random weights repeat nothing on demand: so ask once,
    then plant (last prompt token, first answer token) at the head of the
    prompt — two far-away tokens rarely change the first answer — and the
    drafter finds that pair at the first decode tick. Returns the answered
    row and whether the verify program holds the kernel."""
    import jax.numpy as jnp

    from paddle_tpu.serving import ServingEngine

    spec = ServingEngine(model, max_slots=sz["slots"], spec_k=sz["spec_k"])
    for attempt in range(1, 6):
        t0 = time.perf_counter()
        out = spec.generate([prompt],
                            max_new_tokens=sz["new"])[0][len(prompt):]
        st = spec.stats()["speculative"]
        say("serve", f"speculative request, attempt {attempt} (spec_k "
                     f"{sz['spec_k']}): {time.perf_counter() - t0:.1f} s, "
                     f"{st['ticks']} verify ticks, {st['proposed']} drafted, "
                     f"{st['accepted']} accepted, {st['rollbacks']} rollbacks")
        if st["ticks"]:
            break
        prompt = [prompt[-1], out[0]] + prompt[2:]
    _require(st["ticks"] >= 1, "no verify window ran")
    _, _, pv, bv = spec._functional()
    toks, tables, lens, temps, step_seed = spec._dev
    width = 1 + sz["spec_k"]
    verify = _has_kernel(spec._spec_jit(width, False).lower(
        pv, bv, jnp.zeros((sz["slots"], width), jnp.int32), spec.pool.layers,
        tables, lens, jnp.zeros(sz["slots"], jnp.int32), temps, step_seed))
    say("serve", f"multi-query verify kernel in the verify program: {verify}")
    return ("speculative", prompt, out), verify


def phase_serve(sz, seed, on_tpu):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = (getattr(GPTConfig, sz["config"])()
           if isinstance(sz["config"], str) else GPTConfig(**sz["config"]))
    paddle.seed(seed)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    say("serve", f"GPT {n_params / 1e6:.0f}M params bf16: hidden "
                 f"{cfg.hidden_size}, {cfg.num_layers} layers, "
                 f"{cfg.num_heads} heads, "
                 f"{cfg.max_position_embeddings} positions")

    rng = np.random.RandomState(seed)
    eos = cfg.vocab_size - 1

    def tokens(n):
        return [int(t) for t in rng.randint(0, eos, n)]

    def family(tag):
        """A long prompt, a second one sharing its first `shared` tokens,
        and three short ones."""
        long_a = tokens(sz["long"])
        return {
            f"{tag}long_a": long_a,
            f"{tag}long_b_shared_prefix":
                long_a[:sz["shared"]] + tokens(sz["long"] - sz["shared"]),
            f"{tag}short_a": tokens(sz["short"]),
            f"{tag}short_b": tokens(sz["short"]),
            f"{tag}short_streamed": tokens(sz["short"]),
        }

    # the warm family repeats the first one's shapes with new tokens, so its
    # waves reuse every compiled program
    prompts = {**family(""), **family("warm_")}

    # each engine's pool is freed (with the helper's locals) before the next
    rows, paged = _serve_over_http(model, sz, prompts, eos)
    _free()
    row, verify = _serve_speculative(model, sz, tokens(sz["long"]))
    _free()
    _check_against_reference(model, rows + [row], sz["new"], {
        "short_streamed": eos, "warm_short_streamed": eos})
    if on_tpu:
        _require(paged, "the decode program holds no paged-attention kernel")
        _require(verify, "the verify program holds no multi-query kernel")


# ----------------------------------------------------------------- kernels
def phase_kernels(sz, seed, on_tpu):
    """Pallas kernels the train and serve phases do not execute, against
    jax.numpy references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import interpret_mode
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update
    from paddle_tpu.ops.pallas.fused_norm import fused_rms_norm
    from paddle_tpu.ops.pallas.rope import fused_rope

    interp = interpret_mode()
    rng = np.random.default_rng(seed)
    b, s, h, d = sz["b"], sz["s"], sz["h"], sz["d"]

    def check(name, fn, ref, rtol, *args):
        """Worst output leaf of max |kernel - reference| / max(1, |reference|)."""
        got = jax.jit(fn)(*args)
        want = jax.jit(ref)(*args)
        err = 0.0
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            err = max(err, float(jnp.max(jnp.abs(g - w))
                                 / jnp.maximum(1.0, jnp.max(jnp.abs(w)))))
        say("kernels", f"{name}: max |kernel - reference| relative to the "
                       f"largest reference value = {err:.3e} (bound {rtol})")
        _require(math.isfinite(err) and err <= rtol,
                 f"{name}: {err} > {rtol}")

    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
               for _ in range(3))

    def attn_ref(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1),
                          v.astype(jnp.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, None, True, interpret=interp)

    def sq(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2)

    # bounds: a few bf16 roundings (2^-8 each) of the largest value
    check(f"flash_attention fwd d{d} bf16", flash, attn_ref, 2e-2, q, k, v)
    check(f"flash_attention bwd d{d} bf16", jax.grad(sq(flash), (0, 1, 2)),
          jax.grad(sq(attn_ref), (0, 1, 2)), 5e-2, q, k, v)

    x = jnp.asarray(rng.standard_normal((sz["rows"], sz["hidden"])),
                    jnp.bfloat16)
    w = jnp.asarray(1 + 0.1 * rng.standard_normal(sz["hidden"]), jnp.bfloat16)

    def rms_ref(x, w):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
        return y * w.astype(jnp.float32)

    def rms(x, w):
        return fused_rms_norm(x, w, 1e-6, interp)

    check(f"fused_rms_norm fwd hidden {sz['hidden']} bf16", rms, rms_ref,
          2e-2, x, w)
    check(f"fused_rms_norm bwd hidden {sz['hidden']} bf16",
          jax.grad(sq(rms), (0, 1)), jax.grad(sq(rms_ref), (0, 1)), 2e-2,
          x, w)

    pos = np.arange(s)[:, None] / (10000 ** (np.arange(0, d, 2) / d))
    ang = np.concatenate([pos, pos], axis=1)
    cos, sin = (jnp.asarray(f(ang), jnp.float32) for f in (np.cos, np.sin))

    def rope_ref(q, k, cos, sin):
        def one(x):
            xf = x.astype(jnp.float32)
            rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
            return (xf * cos[None, :, None, :]
                    + rot * sin[None, :, None, :]).astype(x.dtype)
        return one(q), one(k)

    check("fused_rope bf16",
          lambda q, k, c, s_: fused_rope(q, k, c, s_, interpret=interp),
          rope_ref, 2e-2, q, k, cos, sin)

    n = sz["n_adamw"]              # not a multiple of the kernel's chunk
    p, g = (jnp.asarray(rng.standard_normal(n), jnp.float32)
            for _ in range(2))
    m = jnp.zeros(n, jnp.float32)

    def adamw_ref(p, g, m, v, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, wd=0.01):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        up = (m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + eps)
        return p - lr * (up + wd * p), m, v

    check("fused_adamw flat fp32",
          lambda p, g, m, v: fused_adamw_update(
              p, g, m, v, lr=1e-3, weight_decay=0.01, interpret=interp),
          adamw_ref, 1e-5, p, g, m, m)


# --------------------------------------------------------------- four chips
def _state_bytes_per_device(step):
    """Bytes of parameters + optimizer state each device holds, from the
    arrays' own shards."""
    import jax

    per = {}
    leaves = [p._value for p in step.params] + \
        jax.tree_util.tree_leaves(step.opt_state)
    total = 0
    for a in leaves:
        if not hasattr(a, "addressable_shards"):
            continue
        total += a.size * a.dtype.itemsize
        for sh in a.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + \
                sh.data.size * sh.data.dtype.itemsize
    return per, total


def _two_train_steps(sz, seed, mesh, label):
    """Two TrainStep steps of the GPT on one device (mesh None) or under
    `mesh`. Returns the losses, the post-step parameter fingerprint and the
    state bytes per device; the model is freed on return."""
    import jax

    from __graft_entry__ import _fingerprint

    model, step, ids, n_params = _gpt_train_step(sz, seed, mesh=mesh)
    t0 = time.perf_counter()
    losses = [float(jax.block_until_ready(step(ids)._value))
              for _ in range(2)]
    say("chips4", f"{label}: GPT {n_params / 1e6:.1f}M, losses "
                  f"{losses[0]:.5f} {losses[1]:.5f}, compile + two steps "
                  f"{time.perf_counter() - t0:.1f} s")
    return (losses, _fingerprint(model.parameters()),
            *_state_bytes_per_device(step))


def phase_sharded_train(sz, seed, on_tpu):
    """TrainStep on the 355M model under a dp x mp mesh over four devices
    (as __graft_entry__.dryrun_multichip phase A does on virtual devices),
    two steps, against the same steps on one device."""
    import jax

    import paddle_tpu.distributed as dist

    devices = jax.devices()[:4]
    dist.set_mesh(None)
    loss_1, fp_1, per_1, total = _two_train_steps(sz, seed, None,
                                                  "one device")
    say("chips4", f"one device: parameters + optimizer state {total} bytes "
                  f"on devices {per_1}")
    _free()

    mesh = dist.build_mesh(dp=2, mp=2, devices=devices)
    dist.set_mesh(mesh)
    try:
        loss_4, fp_4, per_4, total_4 = _two_train_steps(
            sz, seed, mesh, "mesh dp=2 x mp=2")
    finally:
        dist.set_mesh(None)
    say("chips4", f"mesh: parameters + optimizer state {total_4} bytes in "
                  f"all; bytes held per device: {per_4}")
    for d in devices:
        st = d.memory_stats() or {}
        say("chips4", f"device {d.id} memory_stats: bytes_in_use "
                      f"{st.get('bytes_in_use')}, peak_bytes_in_use "
                      f"{st.get('peak_bytes_in_use')}")
    d_loss = [abs(a - b) for a, b in zip(loss_4, loss_1)]
    say("chips4", f"|loss - one device| = {d_loss[0]:.3e} then "
                  f"{d_loss[1]:.3e} (bound "
                  f"{SHARDED_LOSS_RTOL * abs(loss_1[0]):.3e}); "
                  f"|fingerprint - one device| = {abs(fp_4 - fp_1):.3e} "
                  f"(bound {SHARDED_FINGERPRINT_RTOL * fp_1:.3e})")
    _require(all(map(math.isfinite, loss_4 + [fp_4])), "not finite")
    _require(loss_4[1] < loss_4[0], "the sharded loss did not fall")
    _require(all(d <= SHARDED_LOSS_RTOL * abs(ref)
                 for d, ref in zip(d_loss, loss_1)),
             "a sharded loss is outside the bound")
    _require(abs(fp_4 - fp_1) <= SHARDED_FINGERPRINT_RTOL * fp_1,
             "post-step parameter fingerprint is outside the bound")
    _require(len(per_4) == 4 and min(per_4.values()) > 0,
             f"state is not on all four devices: {per_4}")
    # with mp=2 every matrix of a block and the embedding are halved; what
    # replicates (norms, biases, positions) is a small remainder
    _require(max(per_4.values()) < 0.75 * total_4,
             f"a device holds most of the state: {per_4} of {total_4}")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever jax finds; never reports ok")
    args = ap.parse_args(argv)

    device = {"platform": None, "kind": None, "count": 0}
    failed = []
    try:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        on_tpu = device["platform"] == "tpu"
        print(f"jax {jax.__version__}: {device}", flush=True)
        if not on_tpu and not args.rehearse:
            raise RuntimeError(
                f"chip_smoke needs a TPU and jax found {device['platform']!r}")
        if len(devs) < args.chips:
            raise RuntimeError(
                f"--chips {args.chips} needs {args.chips} devices, jax "
                f"reports {len(devs)}")

        import paddle_tpu as paddle
        from paddle_tpu.jit import enable_persistent_cache
        from paddle_tpu.ops import pallas
        from paddle_tpu.quantization import weight_only

        if on_tpu:
            print(f"compile cache: {enable_persistent_cache()}", flush=True)
        else:       # a rehearsal off the chip: nothing worth caching
            paddle.set_flags({"pallas_interpret": True})
        # selections the code makes from the platform: which side ran
        print(f"selected by platform: pallas_enabled={pallas.pallas_enabled()}"
              f" (interpret={pallas.interpret_mode()}), eager fused AdamW="
              f"{bool(paddle.get_flags('use_fused_adamw')['use_fused_adamw'] and on_tpu)}, "
              f"int8 dequant cache={weight_only._dequant_cache_enabled()}",
              flush=True)

        from paddle_tpu import native

        # built by g++ on first use; this path only mirrors spans into it
        # while a profiler session is open, so it runs without it too
        print(f"native library (not needed here): "
              f"{'available' if native.available() else 'not built'}",
              flush=True)

        sizes = SIZES["tiny" if args.rehearse else "real"]
        phases = ([("chips4", phase_sharded_train, sizes["train"])]
                  if args.chips == 4 else
                  [("train", phase_train, sizes["train"]),
                   ("serve", phase_serve, sizes["serve"]),
                   ("kernels", phase_kernels, sizes["kernels"])])
        for name, fn, sz in phases:
            t0 = time.perf_counter()
            try:
                fn(sz, args.seed, on_tpu)
                say(name, f"PASSED in {time.perf_counter() - t0:.1f} s")
            except Exception:  # noqa: BLE001 - every phase gets its turn
                failed.append(name)
                say(name, "FAILED\n" + traceback.format_exc())
            _free()
        error = ("failed: " + ", ".join(failed) if failed else
                 "a rehearsal is not a chip run" if args.rehearse else None)
    except Exception as e:  # noqa: BLE001 - the last line must still print
        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"

    if args.chips == 4 and device["count"] >= 4:
        device["count"] = 4
    if error is None:
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": False, "device": device, "error": error}),
          flush=True)
    return 1


if __name__ == "__main__":
    sys.exit(main())
