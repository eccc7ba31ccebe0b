"""The one traffic generator. A mix is a data file (traffic/<mix>.json) of
parameters; everything here is a pure function of (mix, seed, sizes).

Every seed gets the SAME multiset of sizes and arrival gaps in another order:
lengths are dealt from the mix's weights by largest remainder, output caps
and gaps are the distribution's quantiles, and the seed only permutes them
and draws the token ids. So runs on different seeds do the same work. A mix
with "order_seed" goes further: the order is the mix's own, the same on every
seed, and the run's seed draws the token ids alone.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def apportion(values, weights, n: int) -> list:
    """n items over `values` in proportion to `weights` (largest remainder)."""
    w = np.asarray(weights, float)
    q = w / w.sum() * n
    base = np.floor(q).astype(int)
    for i in np.argsort(-(q - base), kind="stable")[:n - int(base.sum())]:
        base[i] += 1
    out = []
    for v, k in zip(values, base):
        out += [int(v)] * int(k)
    return out


def quantile_draws(spec: dict, n: int) -> list:
    """The n mid-quantiles of the distribution in `spec`, as whole numbers."""
    qs = (np.arange(n) + 0.5) / max(n, 1)
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        x = [float(spec["median"]) * math.exp(float(spec["sigma"]) * nd.inv_cdf(q))
             for q in qs]
    elif spec["dist"] == "uniform":
        lo, hi = float(spec["min"]), float(spec["max"])
        x = [lo + (hi - lo) * q for q in qs]
    elif spec["dist"] == "fixed":
        x = [float(spec["value"])] * n
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    lo, hi = int(spec.get("min", 1)), int(spec.get("max", 1 << 30))
    return [int(min(hi, max(lo, round(v)))) for v in x]


def exponential_gaps(n: int, rate: float) -> list:
    """n gaps, the mid-quantiles of Exp(rate), rescaled to mean 1/rate."""
    qs = (np.arange(n) + 0.5) / max(n, 1)
    g = -np.log1p(-qs)
    return list(g / g.mean() / rate) if n else []


def train_batches(seed: int, vocab: int, batch: int, seq: int):
    """An endless stream of [batch, seq] int32 token ids; every row differs."""
    rng = rng_for(seed, 1)
    while True:
        yield rng.integers(0, vocab, (batch, seq), dtype=np.int32)


def _tokens(rng, n, vocab):
    # the last id is the eos every request carries; prompts never hold it
    return [int(t) for t in rng.integers(0, vocab - 1, n)]


def sessions(mix: dict, seed: int, n_sessions: int, vocab: int,
             epoch: int = 0) -> list:
    """n_sessions sessions, each a list of `turns` requests
    {"prompt", "shared", "max_new_tokens"}: the shared tokens (a document, a
    system prompt) lead every turn's prompt, followed by that turn's fresh
    tokens. Sizes come from the fixed multisets, the seed permutes them."""
    turns = int(mix["turns"])
    rng = rng_for(seed, 2, epoch)
    # "order_seed": the sizes come in one order whatever the run's seed,
    # which then only draws the token ids
    order = rng_for(mix["order_seed"], 2, epoch) if "order_seed" in mix else rng
    shared = apportion(mix["shared_tokens"]["values"],
                       mix["shared_tokens"]["weights"], n_sessions)
    fresh = apportion(mix["fresh_tokens"]["values"],
                      mix["fresh_tokens"]["weights"], n_sessions * turns)
    outs = quantile_draws(mix["output_tokens"], n_sessions * turns)
    shared = [shared[i] for i in order.permutation(len(shared))]
    fresh = [fresh[i] for i in order.permutation(len(fresh))]
    outs = [outs[i] for i in order.permutation(len(outs))]
    result = []
    for s in range(n_sessions):
        doc = _tokens(rng, shared[s], vocab)
        reqs = []
        for t in range(turns):
            k = s * turns + t
            reqs.append({"prompt": doc + _tokens(rng, fresh[k], vocab),
                         "shared": shared[s], "max_new_tokens": outs[k]})
        result.append(reqs)
    return result


def open_loop_schedule(mix: dict, seed: int, seconds: float, vocab: int):
    """[(due seconds from the window's start, request)] for an open loop at
    the mix's fixed rate: round(rate x seconds) single-turn sessions whose
    gaps are the exponential's quantiles in the seed's order. Sessions of
    several turns are sent turn after turn at the same gaps."""
    rate = float(mix["rate_per_s"])
    turns = int(mix["turns"])
    n = max(1, int(round(rate * seconds)))
    n_sessions = -(-n // turns)
    flat = [r for s in sessions(mix, seed, n_sessions, vocab) for r in s][:n]
    gaps = exponential_gaps(n, rate)
    order = rng_for(mix.get("order_seed", seed), 3)
    gaps = [gaps[i] for i in order.permutation(n)]
    # the schedule spans the window: the first request is due at half a gap
    due = np.cumsum(gaps) - 0.5 * gaps[0]
    return list(zip([float(d) for d in due], flat))


def closed_loop_clients(mix: dict, seed: int, vocab: int, epoch: int = 0,
                        sessions_per_client: int = 4):
    """For each client the sessions it works through in turn; a client that
    runs out takes the next epoch's (the same multiset in another order)."""
    c = int(mix["clients"])
    allsess = sessions(mix, seed, c * sessions_per_client, vocab, epoch)
    return [allsess[i::c] for i in range(c)]


def warmup_classes(mix: dict) -> list:
    """Every (shared, fresh) length class the mix can produce."""
    return [(int(s), int(f)) for s in mix["shared_tokens"]["values"]
            for f in mix["fresh_tokens"]["values"]]
