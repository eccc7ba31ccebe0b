import collections
import json
import os

import numpy as np
import pytest

from benchmark.harness import manifest, traffic

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(manifest.BENCH_DIR, "traffic")))


def _mix(name):
    return manifest.traffic(name)


def test_train_batches_are_a_pure_function_of_the_seed():
    a = traffic.train_batches(7, 50304, 8, 64)
    b = traffic.train_batches(7, 50304, 8, 64)
    c = traffic.train_batches(8, 50304, 8, 64)
    x, y, z = next(a), next(b), next(c)
    assert x.dtype == np.int32 and x.shape == (8, 64)
    assert (x == y).all() and (x != z).any()
    assert (next(a) == next(b)).all()
    # rows all differ
    assert len({r.tobytes() for r in x}) == 8


@pytest.mark.parametrize("name", [m for m in MIXES if _mix(m)["kind"] == "open_loop"])
def test_open_loop_same_seed_same_schedule_other_seed_same_sizes(name):
    mix = _mix(name)
    a = traffic.open_loop_schedule(mix, 3, 40, 50304)
    b = traffic.open_loop_schedule(mix, 3, 40, 50304)
    c = traffic.open_loop_schedule(mix, 2**31 + 11, 40, 50304)
    assert json.dumps(a) == json.dumps(b)
    assert json.dumps(a) != json.dumps(c)
    if "order_seed" in mix:     # the same sizes and gaps in the same order
        assert [(d, len(r["prompt"]), r["max_new_tokens"]) for d, r in a] == \
            [(d, len(r["prompt"]), r["max_new_tokens"]) for d, r in c]

    def sizes(s):
        return (collections.Counter(len(r["prompt"]) for _, r in s),
                collections.Counter(r["max_new_tokens"] for _, r in s),
                sorted(round(g, 9) for g in np.diff([0.0] + [d for d, _ in s])[1:]))

    sa, sc = sizes(a), sizes(c)
    assert sa[0] == sc[0] and sa[1] == sc[1]
    # the same set of gaps in another order (but for the first, halved)
    assert len(a) == round(mix["rate_per_s"] * 40)
    assert all(0 <= d < 40.5 for d, _ in a)
    assert all(r["prompt"] and max(r["prompt"]) < 50303 for _, r in a)


@pytest.mark.parametrize("name", [m for m in MIXES if _mix(m)["kind"] == "closed_loop"])
def test_closed_loop_sessions_share_their_document(name):
    mix = _mix(name)
    clients = traffic.closed_loop_clients(mix, 5, 50304)
    again = traffic.closed_loop_clients(mix, 5, 50304)
    assert json.dumps(clients) == json.dumps(again)
    assert len(clients) == mix["clients"]
    for sess in clients[0]:
        assert len(sess) == mix["turns"]
        n = sess[0]["shared"]
        assert n in mix["shared_tokens"]["values"]
        assert all(r["prompt"][:n] == sess[0]["prompt"][:n] for r in sess)
        assert all(len(r["prompt"]) - n in mix["fresh_tokens"]["values"] for r in sess)
    other = traffic.closed_loop_clients(mix, 6, 50304)
    if "order_seed" in mix:     # the same sizes in the same order, other tokens
        assert [[len(r["prompt"]) for s in c for r in s] for c in clients] == \
            [[len(r["prompt"]) for s in c for r in s] for c in other]
        assert clients[0][0][0]["prompt"] != other[0][0][0]["prompt"]
    flat = lambda cs: sorted((r["shared"], len(r["prompt"]), r["max_new_tokens"])  # noqa: E731
                             for c in cs for s in c for r in s)
    assert sorted(r[0] for r in flat(clients)) == sorted(r[0] for r in flat(other))
    assert sorted(r[2] for r in flat(clients)) == sorted(r[2] for r in flat(other))


def test_apportion_and_quantiles():
    assert traffic.apportion([1, 2, 3], [0.5, 0.25, 0.25], 8) == [1] * 4 + [2] * 2 + [3] * 2
    assert len(traffic.apportion([32, 64], [0.15, 0.85], 7)) == 7
    q = traffic.quantile_draws({"dist": "lognormal", "median": 40, "sigma": 0.9,
                                "min": 8, "max": 256}, 101)
    assert min(q) >= 8 and max(q) <= 256 and q[50] == 40
    g = traffic.exponential_gaps(100, 2.0)
    assert abs(sum(g) - 50.0) < 1e-9
