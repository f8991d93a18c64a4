"""GP posterior serving subsystem (ISSUE 5; DESIGN.md §12).

Covers the serving cache key semantics (θ / chart shape / dtype policy
must miss, identical traffic must hit), slab-packing parity against a
per-request loop, the streaming Welford moment path, and the warm-path
speedup acceptance bar (identical-shape batch >= 5x faster after the
first, with no retrace and no matrix rebuild).
"""
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import ICR, matern32, regular_chart
from repro.core.vi import Posterior, map_posterior
from repro.kernels import dispatch
from repro.launch.serve_gp import (
    GPFieldServer,
    GPRequest,
    demo_posterior,
    mixed_requests,
    scenario_chart,
)

CHART = regular_chart(32, 3, boundary="reflect")  # 256-pt 1-D, fast


def _posterior(theta=None, chart=CHART, dtype_policy=None, seed=0):
    icr = ICR(chart=chart, kernel=matern32, use_pallas=True,
              dtype_policy=dtype_policy)
    theta = {"rho": 8.0} if theta is None else theta
    key = jax.random.PRNGKey(seed)
    mean = icr.init_xi(key, dtype=jnp.float32)
    log_std = [jnp.full_like(m, -1.0) for m in mean]
    return Posterior(icr=icr, mean=mean, log_std=log_std, theta=theta)


# -- slab packing ---------------------------------------------------------------
def test_packed_heterogeneous_batch_matches_per_request_loop():
    """Parity at 1e-5: a packed mixed batch == the same requests served one
    row at a time (slab=1 degenerates to a per-request loop), == a manual
    reference applying sqrt(K) to each row's ξ draw directly."""
    post = _posterior()
    reqs = lambda: [GPRequest(kind="sample", n=3, seed=11),
                    GPRequest(kind="moments", n=5, seed=12),
                    GPRequest(kind="sample", n=2, seed=13)]

    packed = reqs()
    GPFieldServer(post, slab=4).run(packed)
    looped = reqs()
    GPFieldServer(post, slab=1).run(looped)

    # manual reference: the documented (seed, row) eps contract
    mats = post.matrices()
    icr = post.icr

    def row_field(seed, row):
        k = jax.random.fold_in(jax.random.PRNGKey(seed), row)
        ks = jax.random.split(k, len(post.mean))
        xi = [m + s * jax.random.normal(kk, m.shape, m.dtype)
              for kk, m, s in zip(ks, post.mean, post.std())]
        return np.asarray(icr.apply_sqrt(mats, xi))

    for p, l in zip(packed, looped):
        assert p.done and l.done and p.error is None
        if p.kind == "sample":
            assert len(p.fields) == p.n
            for row, (fp, fl) in enumerate(zip(p.fields, l.fields)):
                np.testing.assert_allclose(fp, fl, rtol=1e-5, atol=1e-5)
                np.testing.assert_allclose(fp, row_field(p.seed, row),
                                           rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_allclose(p.mean, l.mean, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(p.std, l.std, rtol=1e-5, atol=1e-5)
            draws = np.stack([row_field(p.seed, r) for r in range(p.n)])
            np.testing.assert_allclose(p.mean, draws.mean(0),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(p.std, draws.std(0),
                                       rtol=1e-4, atol=1e-5)


def test_welford_moments_stream_across_slabs():
    """An MC budget far larger than the slab exercises the Chan merge path;
    the result must equal the one-shot mean/std over the same draws."""
    post = _posterior()
    req = GPRequest(kind="moments", n=13, seed=3)  # 13 rows through slab 4
    srv = GPFieldServer(post, slab=4)
    srv.run([req])
    assert srv.slabs_run == 4  # ceil(13/4)
    sample = GPRequest(kind="sample", n=13, seed=3)
    GPFieldServer(post, slab=4).run([sample])
    draws = np.stack(sample.fields)
    np.testing.assert_allclose(req.mean, draws.mean(0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(req.std, draws.std(0), rtol=1e-5, atol=1e-6)


def test_map_posterior_moments_are_delta():
    """A MAP export is a delta posterior: served mean == sqrt(K) ξ̂ exactly,
    served std == 0."""
    icr = ICR(chart=CHART, kernel=matern32, use_pallas=True)
    xi_hat = icr.init_xi(jax.random.PRNGKey(5), dtype=jnp.float32)
    post = map_posterior(icr, xi_hat, theta={"rho": 8.0})
    req = GPRequest(kind="moments", n=6, seed=1)
    GPFieldServer(post, slab=4).run([req])
    want = np.asarray(icr.apply_sqrt(icr.matrices_cached(post.theta), xi_hat))
    np.testing.assert_allclose(req.mean, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(req.std, 0.0, atol=1e-5)


def test_bad_request_rejected():
    srv = GPFieldServer(_posterior(), slab=2)
    bad = GPRequest(kind="quantiles", n=3)
    zero = GPRequest(kind="sample", n=0)
    wide = GPRequest(kind="sample", n=1, seed=2**31)  # int32 overflow
    ok = GPRequest(kind="sample", n=1)
    srv.run([bad, zero, wide, ok])
    assert bad.done and bad.error and zero.done and zero.error
    assert wide.done and wide.error
    assert ok.done and ok.error is None and len(ok.fields) == 1


# -- cache key semantics --------------------------------------------------------
def test_cache_hits_and_misses():
    """(chart geometry, θ, dtype policy) is the cache key: identical
    traffic hits; changing any component misses and rebuilds."""
    srv = GPFieldServer(_posterior(theta={"rho": 8.0}), slab=4)
    assert (srv.cache_misses, srv.cache_hits) == (1, 0)
    srv.run(mixed_requests(2, 4))
    srv.run(mixed_requests(2, 4))
    assert srv.cache_misses == 1 and srv.cache_hits == 2

    # θ change: miss (fresh matrices; same shapes, so no new jit cache is
    # strictly needed — the server still isolates per-key executables)
    srv.set_posterior(_posterior(theta={"rho": 2.0}))
    assert srv.cache_misses == 2
    # back to the first θ from a *re-fit* (new Posterior object, new ICR
    # instance, equal chart/θ/policy values): hit
    srv.set_posterior(_posterior(theta={"rho": 8.0}, seed=9))
    assert srv.cache_misses == 2 and srv.cache_hits == 3

    # chart shape change: miss
    srv.set_posterior(_posterior(chart=regular_chart(64, 3,
                                                     boundary="reflect")))
    assert srv.cache_misses == 3
    # dtype policy change: miss
    srv.set_posterior(_posterior(dtype_policy="bf16"))
    assert srv.cache_misses == 4


def test_kernel_defaults_are_part_of_the_cache_key():
    """θ baked into kernel defaults (theta=None) must not collide: two
    posteriors differing only in with_defaults(rho=...) are different
    matrices — a hit here served the wrong field."""
    from repro.launch.serve_gp import demo_posterior

    srv = GPFieldServer(demo_posterior(CHART, 8.0), slab=2)
    req_a = GPRequest(kind="sample", n=1, seed=1)
    srv.run([req_a])
    srv.set_posterior(demo_posterior(CHART, 0.5))
    assert srv.cache_misses == 2  # not a hit
    req_b = GPRequest(kind="sample", n=1, seed=1)
    srv.run([req_b])

    fresh = GPFieldServer(demo_posterior(CHART, 0.5), slab=2)
    req_f = GPRequest(kind="sample", n=1, seed=1)
    fresh.run([req_f])
    np.testing.assert_allclose(req_b.fields[0], req_f.fields[0],
                               rtol=1e-6, atol=1e-6)
    assert np.abs(req_b.fields[0] - req_a.fields[0]).max() > 0.1


def test_matrices_cached_on_icr():
    icr = ICR(chart=CHART, kernel=matern32, use_pallas=True)
    m1 = icr.matrices_cached({"rho": 4.0})
    m2 = icr.matrices_cached({"rho": 4.0})
    assert m1 is m2
    assert icr.matrices_cache_stats == {"hits": 1, "misses": 1}
    m3 = icr.matrices_cached({"rho": 5.0})
    assert m3 is not m1
    assert icr.matrices_cache_stats == {"hits": 1, "misses": 2}
    # traced θ bypasses the cache (matrices rebuilt inside the trace)
    jax.jit(lambda r: icr.matrices_cached({"rho": r})["sqrt0"])(4.0)
    assert icr.matrices_cache_stats == {"hits": 1, "misses": 2}


def test_plan_cached():
    dispatch.plan_cache_clear()
    p1 = dispatch.plan_cached(CHART, samples=4)
    p2 = dispatch.plan_cached(CHART, samples=4)
    assert p1 is p2
    assert dispatch.plan_cache_stats == {"hits": 1, "misses": 1}
    p3 = dispatch.plan_cached(CHART, samples=4, dtype="bfloat16")
    assert p3 is not p1
    assert dispatch.plan_cache_stats["misses"] == 2
    assert p1 == dispatch.plan(CHART, samples=4)


# -- warm-path acceptance (ISSUE 5) ---------------------------------------------
def test_warm_identical_batch_at_least_5x_faster():
    """After the first batch, an identical-shape batch must run >= 5x
    faster wall-clock: no retrace (the jitted slab executable's cache stays
    at one entry), no matrix rebuild (ICR matrices cache reports a hit,
    not a miss), and the server's executable cache hits."""
    post = demo_posterior(scenario_chart("dust", quick=True), 0.5)
    srv = GPFieldServer(post, slab=8)
    t0 = time.perf_counter()
    srv.run(mixed_requests(3, 8))
    cold = time.perf_counter() - t0

    mats_misses = post.icr.matrices_cache_stats["misses"]
    hits_before = srv.cache_hits
    t0 = time.perf_counter()
    srv.run(mixed_requests(3, 8))
    warm = time.perf_counter() - t0

    assert cold >= 5.0 * warm, (cold, warm)
    assert srv.cache_hits > hits_before
    assert post.icr.matrices_cache_stats["misses"] == mats_misses
    fn = srv._entry["fn"].variant(False)  # these requests supply no ξ
    if hasattr(fn, "_cache_size"):  # retrace detector (jax >= 0.4)
        assert fn._cache_size() == 1
