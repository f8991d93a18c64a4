"""The ``open`` and ``closed`` modes: posterior samples and moments from
``GPFieldServer``.

The window drives ``GPFieldServer.step`` on a live queue. Completed
requests leave the queue and their fields are freed, except those the
check keeps: in the open loop, requests drawn from the seed before the
window opens; in the closed loop, one completed request of each kind,
drawn from the seed over all of that kind's completions. Once
the window has closed and the program's state is freed, the reference
recomputes each kept answer: the server's (seed, row) draw, every level,
and for ``moments`` the mean and population std over the rows.
"""
from __future__ import annotations

import collections
import time

import numpy as np
import jax
import jax.numpy as jnp

import counts
import harness
import oracle
import program
import traffic as gen

DRAIN_S = 60.0  # how long past the window an answer may still come
# the closed loop's in-flight requests at the close: two clients' largest
# (16 rows each), eight 4-row slabs of ~9 s, with room
DRAIN_CLOSED_S = 120.0


def _posterior(cfg, icr, seed):
    k = jax.random.PRNGKey(int(gen.rng(seed, "posterior").integers(
        0, 2 ** 31 - 1)))
    return program.posterior(cfg, icr, k)


def _warm(srv, slab: int):
    """One slab through the served executable: the only shape the window
    uses."""
    from repro.launch.serve_gp import GPRequest

    req = GPRequest(kind="sample", n=slab, seed=0)
    while not req.done:
        srv.step([req])
    if req.error:
        raise RuntimeError(f"warm-up request failed: {req.error}")


def _answer(req) -> dict:
    if req.kind == "sample":
        return {"kind": "sample", "seed": req.seed, "n": req.n,
                "fields": list(req.fields)}
    return {"kind": "moments", "seed": req.seed, "n": req.n,
            "mean": req.mean, "std": req.std}


def _free(req):
    req.fields, req.mean, req.std = [], None, None


def open_loop(srv, schedule, seconds, keep, tracer, clock=time.perf_counter):
    """Open-loop arrivals into ``srv``: returns per-request latency (inf
    for a failed or unfinished request), the kept answers, and the
    server's rows/slabs over the run."""
    pending = collections.deque(enumerate(schedule))
    queue, lat, kept, live = [], {}, {}, {}
    rows0, slabs0 = srv.rows_served, srv.slabs_run
    t0 = clock()
    while pending or queue:
        now = clock() - t0
        while pending and pending[0][1].due <= now:
            i, r = pending.popleft()
            req = program.request(r)
            live[id(req)] = i
            queue.append(req)
        if queue:
            with tracer.span("bench.step"):
                srv.step(queue)
            done_t = clock() - t0
            for req in [q for q in queue if q.done]:
                i = live.pop(id(req))
                lat[i] = (np.inf if req.error
                          else done_t - schedule[i].due)
                if i in keep and not req.error:
                    kept[i] = _answer(req)
                _free(req)
                queue.remove(req)
            if done_t > seconds + DRAIN_S:
                break
        elif pending:
            with tracer.span("bench.wait"):
                time.sleep(max(0.0, pending[0][1].due - (clock() - t0)))
    for i in range(len(schedule)):
        lat.setdefault(i, np.inf)
    return {"latency": [lat[i] for i in range(len(schedule))],
            "kept": kept, "rows": srv.rows_served - rows0,
            "slabs": srv.slabs_run - slabs0, "span": clock() - t0}


def _settle(out, req) -> bool:
    """Record a finished request in ``out``. Of each kind's ``k``-th
    answer, keep it with chance ``1/k`` from the seed's stream, so that
    the one kept is a uniform draw over all of that kind's answers.
    Returns whether it finished."""
    if not req.done:
        return False
    out["done"] += 1
    out["failed"] += int(bool(req.error))
    if not req.error:
        k = out["answers"][req.kind] = out["answers"].get(req.kind, 0) + 1
        if out["draw"].random() * k < 1.0:
            out["kept"][req.kind] = _answer(req)
    _free(req)
    return True


def closed_loop(srv, seqs, seconds, tracer, draw):
    """Closed-loop clients: each sends its next request when the last one
    completes. The window closes at the end of the first step that ends
    after ``seconds``; ``out["active"]`` holds the requests then in
    flight. ``draw`` picks the answers kept for the check."""
    cursors = [0] * len(seqs)

    def nxt(c):
        r = seqs[c][cursors[c] % len(seqs[c])]
        cursors[c] += 1
        return program.request(r)

    out = {"kept": {}, "answers": {}, "draw": draw, "done": 0, "failed": 0}
    active = [nxt(c) for c in range(len(seqs))]
    rows0, slabs0 = srv.rows_served, srv.slabs_run
    t0 = time.perf_counter()
    while True:
        with tracer.span("bench.step"):
            srv.step(active)
        active = [nxt(c) if _settle(out, r) else r
                  for c, r in enumerate(active)]
        if time.perf_counter() - t0 >= seconds:
            break
    out.update(window=time.perf_counter() - t0, active=active,
               rows=srv.rows_served - rows0, slabs=srv.slabs_run - slabs0)
    return out


def drain(srv, out):
    """Serve the requests in flight at the close to their end (two minutes
    at most): each is an answer that may be drawn for the check."""
    t0 = time.perf_counter()
    active = out.pop("active")
    while active and time.perf_counter() - t0 < DRAIN_CLOSED_S:
        srv.step(active)
        active = [r for r in active if not _settle(out, r)]
    out["failed"] += len(active)


def quantile(latency, q: float) -> float:
    """Nearest-rank ``q`` quantile; a missing answer counts as infinite."""
    xs = sorted(latency)
    return float(xs[max(0, int(np.ceil(q * len(xs))) - 1)])


def p90(latency) -> float:
    return quantile(latency, 0.9)


# -- the check ---------------------------------------------------------------------
def reference_answers(answers, mats, post_mean, post_std, geom,
                      precision: str = oracle.HIGHEST):
    """Yields (answer, reference rows as device arrays)."""
    fwd = oracle.forward_fn(geom, precision)
    for a in answers:
        rows = (fwd(mats, oracle.row_xi(post_mean, post_std, a["seed"], r))
                for r in range(a["n"]))
        yield a, rows


def welford(rows):
    """Mean and population std over device rows, one pass."""
    n, mean, m2 = 0, None, None
    for row in rows:
        n += 1
        if mean is None:
            mean, m2 = row, jnp.zeros_like(row)
        else:
            delta = row - mean
            mean = mean + delta / n
            m2 = m2 + delta * (row - mean)
    return mean, jnp.sqrt(jnp.maximum(m2 / n, 0.0))


def gaps(answers, mats, post_mean, post_std, geom,
         precision: str = oracle.HIGHEST) -> dict:
    """Widest relative L2 gap of a served sample row, and of a moments
    mean or std, against the reference's."""
    sample, moments = 0.0, 0.0
    for a, rows in reference_answers(answers, mats, post_mean, post_std,
                                     geom, precision):
        if a["kind"] == "sample":
            for got, want in zip(a["fields"], rows):
                sample = max(sample, oracle.rel_l2(got, want))
        else:
            mean, std = welford(rows)
            moments = max(moments, oracle.rel_l2(a["mean"], mean),
                          oracle.rel_l2(a["std"], std))
    return {"sample_gap": sample, "moments_gap": moments}


def _checks(limits, answers, mats, post, geom) -> dict:
    got = gaps(answers, mats, post.mean, post.std(), geom)
    return {k: {"value": v, "limit": float(limits[k])}
            for k, v in got.items()}


# -- the runs -----------------------------------------------------------------------
def _setup(cell, seed):
    cfg = cell.config
    geom = oracle.geometry(cfg)
    icr = program.model(cfg)
    post = _posterior(cfg, icr, seed)
    srv = program.server(cfg, post)
    _warm(srv, int(cfg["slab"]))
    return geom, post, srv


def _kept_indices(schedule, k: int, seed: int) -> set:
    """``k`` requests drawn from the seed, as many of each kind as there
    are (up to half of ``k`` each)."""
    g = gen.rng(seed, "check")
    out = set()
    for kind in ("sample", "moments"):
        idx = [i for i, r in enumerate(schedule) if r.kind == kind]
        take = min(len(idx), k // 2)
        out.update(int(i) for i in g.choice(idx, size=take, replace=False))
    return out


def _reading(out, counters, geom) -> dict:
    """What the per-layer readers take besides the trace: the server's
    counters over the window and the least work of one slab."""
    cap = int(counters["capacity"])
    return {"counters": {"rows": out["rows"], "slabs": out["slabs"],
                         "capacity": cap},
            "work": {"refine": counts.refine_work(geom, cap),
                     "step": counts.served_slab_work(geom, cap)}}


def run_open(cell, seed, seconds, tracer, t0, devs) -> dict:
    geom, post, srv = _setup(cell, seed)
    schedule = gen.open_schedule(cell.traffic, seed, seconds)
    keep = _kept_indices(schedule, int(cell.traffic["checked"]), seed)
    setup_s = time.perf_counter() - t0
    harness.log(f"set-up {setup_s:.3f} s; {len(schedule)} requests due")
    with tracer.capture():
        out = open_loop(srv, schedule, seconds, keep, tracer)
    device = harness.device_info(devs)
    counters = srv.metrics()
    del srv
    lat = out["latency"]
    failed = int(sum(not np.isfinite(x) for x in lat))
    harness.log(f"served {len(lat)} requests in {out['span']:.3f} s, "
                f"{out['slabs']} slabs, p50 {quantile(lat, 0.5):.4f} s, "
                f"mean {np.mean(lat):.4f} s, p90 {p90(lat):.4f} s")
    harness.log("latencies (s, in order due): "
                + " ".join(f"{x:.4f}" for x in lat))
    mats = oracle.matrices(cell.config)
    answers = [out["kept"][i] for i in sorted(out["kept"])]
    checks = _checks(cell.limits, answers, mats, post, geom)
    checks["unanswered"] = {"value": float(len(keep) - len(answers)),
                            "limit": 0.0}
    return {
        "e2e": {"request_p50_s": quantile(lat, 0.5), "setup_s": setup_s},
        "attempted": len(lat), "failed": failed, "device": device,
        "checks": checks,
        "reading": dict(_reading(out, counters, geom),
                        latency={"p90": p90(lat)}),
    }


def run_closed(cell, seed, seconds, tracer, t0, devs) -> dict:
    geom, post, srv = _setup(cell, seed)
    seqs = gen.closed_sequences(cell.traffic, seed)
    setup_s = time.perf_counter() - t0
    harness.log(f"set-up {setup_s:.3f} s")
    with tracer.capture():
        out = closed_loop(srv, seqs, seconds, tracer, gen.rng(seed, "check"))
    drain(srv, out)
    device = harness.device_info(devs)
    counters = srv.metrics()
    del srv
    harness.log(f"{out['rows']} rows in {out['window']:.3f} s, "
                f"{out['done']} requests done: {out['answers']}")
    mats = oracle.matrices(cell.config)
    answers = [out["kept"][k] for k in sorted(out["kept"])]
    checks = _checks(cell.limits, answers, mats, post, geom)
    checks["unanswered"] = {
        "value": float(len(set(cell.traffic["mix"]) - set(out["kept"]))),
        "limit": 0.0}
    return {
        "e2e": {"draw_rate": out["rows"] * geom.size / out["window"] / 1e6,
                "setup_s": setup_s},
        "attempted": out["done"], "failed": out["failed"],
        "device": device, "checks": checks,
        "reading": _reading(out, counters, geom),
    }
