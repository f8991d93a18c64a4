"""Spans, counters and named scopes of the GP field server.

``GPFieldServer.step`` runs in the host spans ``serve.step`` >
``serve.admit``/``pack``/``execute``/``fetch``/``scatter``
(``repro.launch.spans``), whose seconds and counts ``metrics()`` reports
as ``phase_s``/``phase_n``; requests carry an ``rid`` and the queue wait
from admission to their first packed row. ``op_scopes()`` ties compiled
instructions to the ``icr.*``/``serve.*`` named scopes of the slab.
"""
import glob
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core import ICR, matern32, regular_chart
from repro.core.vi import Posterior
from repro.launch.serve_gp import GPFieldServer, GPRequest
from repro.launch.spans import span

CHART = regular_chart(32, 3, boundary="reflect")  # 256-pt 1-D, fast
CHILDREN = ("serve.admit", "serve.pack", "serve.execute", "serve.fetch",
            "serve.scatter")


def _server(slab=4, **icr_kw):
    icr = ICR(chart=CHART, kernel=matern32.with_defaults(rho=8.0),
              use_pallas=True, **icr_kw)
    mean = icr.init_xi(jax.random.PRNGKey(0), dtype=jnp.float32)
    post = Posterior(icr=icr, mean=mean,
                     log_std=[jnp.full_like(m, -1.0) for m in mean])
    return GPFieldServer(post, slab=slab)


def _mixed():
    return [GPRequest(kind="sample", n=3, seed=1),
            GPRequest(kind="moments", n=6, seed=2),
            GPRequest(kind="sample", n=1, seed=3)]


def test_span_counts_seconds_also_when_the_body_raises():
    counters = {}
    with span("a", counters, slab=1):
        pass
    with pytest.raises(ValueError):
        with span("a", counters, slab=2):
            raise ValueError("boom")
    seconds, count = counters["a"]
    assert count == 2 and seconds >= 0.0


def test_phase_counters_follow_the_slabs():
    srv = _server()
    reqs = srv.run(_mixed())
    assert all(r.done and r.error is None for r in reqs)
    m = srv.metrics()
    assert m["slabs_run"] == 3  # 10 rows in slabs of 4
    for name in ("serve.execute", "serve.fetch", "serve.scatter"):
        assert m["phase_n"][name] == m["slabs_run"], name
    for name in ("serve.step", "serve.admit", "serve.pack"):
        assert m["phase_n"][name] == m["slabs_run"], name
    children = sum(m["phase_s"][c] for c in CHILDREN)
    assert 0.0 < children <= m["phase_s"]["serve.step"]
    assert set(m["phase_s"]) == set(m["phase_n"]) == {"serve.step",
                                                      *CHILDREN}


def test_queue_wait_zero_alone_positive_behind_a_full_slab():
    srv = _server(slab=2)
    lone = GPRequest(kind="sample", n=1, seed=1)
    srv.run([lone])
    m = srv.metrics()
    assert m["requests_started"] == 1 and m["queue_wait_s"] == 0.0
    assert lone.admitted_s == lone.started_s <= lone.finished_s

    srv = _server(slab=2)
    first = GPRequest(kind="sample", n=2, seed=1)   # fills the first slab
    behind = GPRequest(kind="moments", n=2, seed=2)
    srv.run([first, behind])
    m = srv.metrics()
    assert m["requests_started"] == 2
    assert behind.started_s > behind.admitted_s == first.admitted_s
    assert m["queue_wait_s"] == pytest.approx(
        behind.started_s - behind.admitted_s)
    assert first.finished_s <= behind.finished_s


def test_rids_are_distinct_and_rejections_are_finished():
    srv = _server()
    reqs = _mixed() + [GPRequest(kind="bogus", n=1)]
    srv.run(reqs)
    rids = [r.rid for r in reqs]
    assert None not in rids and len(set(rids)) == len(rids)
    bad = reqs[-1]
    assert bad.error and bad.error.code == "bad-request"
    assert bad.finished_s is not None and bad.started_s is None
    more = [GPRequest(kind="sample", n=1, seed=9)]
    srv.run(more)
    assert more[0].rid > max(rids)


def _host_events(logdir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_profiled_children_sit_inside_their_step(tmp_path):
    srv = _server()
    srv.run([GPRequest(kind="sample", n=1, seed=0)])  # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.run(_mixed())
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    steps = [e for e in events if e[0] == "serve.step"]
    assert len(steps) == 3
    for name in CHILDREN:
        kids = [e for e in events if e[0] == name]
        assert len(kids) == 3, name
        for _, s0, s1, ids in kids:
            parent = [st for st in steps if st[3]["slab"] == ids["slab"]]
            assert len(parent) == 1, (name, ids)
            assert parent[0][1] <= s0 <= s1 <= parent[0][2], name
    packs = sorted((e[3]["slab"], e[3]["rids"]) for e in events
                   if e[0] == "serve.pack")
    rids = [str(r) for _, r in packs]  # the profiler reads "3" as 3
    # 3 + 6 + 1 rows in slabs of 4: requests 2 3 4 (this run's ids)
    assert rids == ["2 3", "3", "3 4"]


def test_op_scopes_name_levels_and_interleave(interpret_backend):
    srv = _server(use_pyramid=False)  # every level its own 1-D launch
    srv.run([GPRequest(kind="sample", n=1, seed=0)])
    scopes = srv.op_scopes()
    paths = set(scopes.values())
    for lvl in range(1, CHART.n_levels + 1):
        assert f"icr.level{lvl}" in paths
        assert f"icr.level{lvl}/icr.interleave" in paths
    assert {"icr.level0", "serve.draw"} <= paths
    text = srv.lowered_slab().compile().as_text()
    for name in scopes:
        assert f"{name} = " in text
