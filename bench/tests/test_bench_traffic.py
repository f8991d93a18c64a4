"""The traffic generator: the same seed, the same work; other seeds, the
same multiset of sizes and gaps in another order."""
import json
from collections import Counter

import numpy as np
import pytest

import _paths
import traffic as gen

TRAFFIC = {p.stem: json.loads(p.read_text())
           for p in (_paths.BENCH / "traffic").glob("*.json")}
BIG = 2 ** 31 + 12345  # seeds reach past 32 signed bits


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_every_traffic_file_is_runnable(name):
    gen.check_traffic(TRAFFIC[name])


def _open():
    return next(t for t in TRAFFIC.values() if t["mode"] == "open")


def _closed():
    return next(t for t in TRAFFIC.values() if t["mode"] == "closed")


def test_open_schedule_is_deterministic_per_seed():
    t = _open()
    assert gen.open_schedule(t, BIG, 30.0) == gen.open_schedule(t, BIG,
                                                                30.0)
    assert gen.open_schedule(t, BIG, 30.0) != gen.open_schedule(t, 7, 30.0)


def test_open_schedule_same_work_for_every_seed():
    t = _open()
    a, b = gen.open_schedule(t, 1, 40.0), gen.open_schedule(t, BIG, 40.0)
    assert Counter((r.kind, r.n) for r in a) == Counter((r.kind, r.n)
                                                        for r in b)
    gaps = lambda s: sorted(np.round(np.diff([r.due for r in s]), 9))
    m = int(round(t["rate"] * 40.0))
    assert len(a) == len(b) == m  # every gap lands inside the window
    assert abs(np.mean(np.diff([r.due for r in a])) - 1 / t["rate"]) \
        < 0.1 / t["rate"]
    assert len(set(gaps(a)) & set(gaps(b))) >= m - 3
    assert all(0 <= r.seed < 2 ** 31 for r in a)
    share = sum(r.kind == "sample" for r in a) / m
    assert abs(share - t["mix"]["sample"]["share"]) <= 1 / m


def test_closed_sequences_are_deterministic_with_the_mix():
    t = _closed()
    a, b = gen.closed_sequences(t, BIG), gen.closed_sequences(t, BIG)
    assert a == b and len(a) == t["clients"]
    c = gen.closed_sequences(t, 3)
    for s, u in zip(a, c):  # the same work in the same order, other seeds
        assert [(r.kind, r.n) for r in s] == [(r.kind, r.n) for r in u]
        assert [r.seed for r in s] != [r.seed for r in u]
    kinds = Counter(r.kind for r in a[0])
    assert kinds["moments"] == round(t["mix"]["moments"]["share"]
                                     * t["per_client"])


def test_closed_clients_open_with_every_kind_of_the_mix():
    t = _closed()
    for seed in (0, 5, BIG):
        seqs = gen.closed_sequences(t, seed)
        assert [s[0].kind for s in seqs] == sorted(t["mix"])[:len(seqs)]


def test_open_schedule_is_one_cycle_from_a_starting_point_per_seed():
    t = _open()
    a, b = gen.open_schedule(t, 1, 40.0), gen.open_schedule(t, BIG, 40.0)
    m = len(a)
    pa, pb = [(r.kind, r.n) for r in a], [(r.kind, r.n) for r in b]
    k = next(k for k in range(m) if pa[k:] + pa[:k] == pb)
    da, db = np.diff([r.due for r in a]), np.diff([r.due for r in b])
    for j in range(m - 1):  # the gap after each request, where a has it
        if (k + j) % m != m - 1:
            assert db[j] == pytest.approx(da[(k + j) % m])
    starts = {gen.open_schedule(t, s, 40.0)[0].n for s in range(20)}
    assert len(starts) > 1  # the seed moves the starting point


def test_generator_streams_are_independent():
    assert gen.rng(5, "open").integers(1 << 30) != gen.rng(5, "check") \
        .integers(1 << 30)


@pytest.mark.parametrize("bad", [{"mode": "burst"}, {"mode": "open"},
                                 {"mode": "open", "rate": 1.0,
                                  "mix": {"condition": {"share": 1,
                                                        "n": [1, 2]}}}])
def test_bad_traffic_is_refused(bad):
    with pytest.raises(ValueError):
        gen.check_traffic(bad)
