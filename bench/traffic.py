"""The one traffic generator: a traffic file's parameters and a seed in,
the work of one run out. The same seed gives the same work; every seed
gets the same sizes and gaps (in the open loop from its own starting
point), so that seeds change which requests come when, not how much work
a run holds.

Modes:

* ``open``   -- open-loop arrivals at a fixed ``rate`` (Poisson gaps, taken
                as the exponential's quantiles) of ``sample`` and
                ``moments`` requests. Gaps, kinds and sizes form one
                pattern in one order for every seed, and the seed turns
                the pattern round to a starting point of its own: in a
                queue the order of the gaps, and not only their multiset,
                sets the tail, so a free shuffle per seed changed the
                work.
* ``closed`` -- ``clients`` closed-loop clients, each with its own request
                sequence; client ``c`` opens with the ``c``-th kind of the
                mix (sorted by name), so every kind is in flight from the
                start. A window holds only a client's first few requests,
                so the kinds and sizes come in one order for every seed,
                and the seed draws only each request's random stream.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

MODES = ("open", "closed")
SEED_LIMIT = 2 ** 31 - 1  # request seeds the server accepts


@dataclasses.dataclass(frozen=True)
class Request:
    due: float  # seconds after the window opens (open loop); 0 otherwise
    kind: str   # "sample" or "moments"
    n: int
    seed: int


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream): any whole seed."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng([int(seed) % 2 ** 63, *tag])


def _kinds(count: int, mix: dict, g: np.random.Generator) -> list:
    """``count`` (kind, n) pairs: each kind's share of ``count`` rounded,
    its sizes cycling through the whole range, the list shuffled."""
    out = []
    kinds = sorted(mix)
    for i, kind in enumerate(kinds):
        share = mix[kind]["share"]
        k = (count - len(out) if i == len(kinds) - 1
             else int(round(share * count)))
        lo, hi = mix[kind]["n"]
        sizes = [lo + j % (hi - lo + 1) for j in range(k)]
        out.extend((kind, n) for n in sizes)
    order = g.permutation(len(out))
    return [out[i] for i in order]


def open_schedule(traffic: dict, seed: int, seconds: float) -> list:
    """Requests due in ``[0, seconds)``: ``round(rate * seconds)`` gaps at
    the exponential's quantiles, each with its kind and size, in one order
    for every seed; the seed picks where in that cycle the run starts and
    draws each request's random stream."""
    rate = float(traffic["rate"])
    m = max(1, int(round(rate * seconds)))
    order = rng(0, "open-order")
    gaps = -np.log1p(-(np.arange(m) + 0.5) / m) / rate
    gaps = gaps[order.permutation(m)]
    kinds = _kinds(m, traffic["mix"], order)
    g = rng(seed, "open")
    turn = (np.arange(m) + int(g.integers(0, m))) % m
    gaps, kinds = gaps[turn], [kinds[i] for i in turn]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    seeds = g.integers(0, SEED_LIMIT, size=m)
    return [Request(float(t), k, int(n), int(s))
            for t, (k, n), s in zip(due, kinds, seeds) if t < seconds]


def closed_sequences(traffic: dict, seed: int) -> list:
    """One request sequence per client, ``per_client`` long, each turned
    to open with its client's kind: the same kinds and sizes for every
    seed, the seed's own request seeds."""
    order, g = rng(0, "closed-order"), rng(seed, "closed")
    kinds = sorted(traffic["mix"])
    out = []
    for c in range(int(traffic["clients"])):
        seq = _kinds(int(traffic["per_client"]), traffic["mix"], order)
        first = kinds[c % len(kinds)]
        j = next((i for i, (k, _) in enumerate(seq) if k == first), 0)
        seq = seq[j:] + seq[:j]
        seeds = g.integers(0, SEED_LIMIT, size=len(seq))
        out.append([Request(0.0, k, int(n), int(s))
                    for (k, n), s in zip(seq, seeds)])
    return out


def check_traffic(traffic: dict):
    """Refuse a traffic file this generator cannot run."""
    mode = traffic.get("mode")
    if mode not in MODES:
        raise ValueError(f"traffic mode {mode!r}: one of {MODES}")
    need = {"open": ("rate", "mix", "checked"),
            "closed": ("clients", "per_client", "mix")}[mode]
    missing = [k for k in need if k not in traffic]
    if missing:
        raise ValueError(f"traffic ({mode}) lacks {missing}")
    for kind, spec in traffic.get("mix", {}).items():
        if kind not in ("sample", "moments"):
            raise ValueError(f"request kind {kind!r}")
        lo, hi = spec["n"]
        if not 1 <= lo <= hi or not 0 <= spec["share"] <= 1:
            raise ValueError(f"mix {kind}: {spec}")
    if mode == "open" and not math.isfinite(float(traffic["rate"])):
        raise ValueError("rate must be finite")
