"""End-to-end driver (the paper's kind of workload): train a GP field +
kernel parameters on noisy observations with the standardized generative
model (paper §3.2) — a few hundred optimizer steps, no kernel inversion.

  field prior : ICR on a 4096-point chart (sqrt(K_ICR) applications only),
                running the fused Pallas path — forward AND backward: every
                optimizer step's gradient goes through the hand-written
                adjoint kernels, never the jnp reference
  theta prior : LogNormal on the kernel scale rho, via inverse-CDF
  inference   : MAP over (xi_field, xi_theta), then mean-field ADVI for
                uncertainties

Run:  PYTHONPATH=src python examples/gp_regression_vi.py [--steps 300]
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    ICR,
    StandardizedModel,
    advi_fit,
    advi_posterior,
    gaussian_log_likelihood,
    lognormal_prior,
    map_fit,
    matern32,
    regular_chart,
)
from repro.data import charted_gp_dataset
from repro.kernels import dispatch
from repro.launch.serve_gp import GPFieldServer, GPRequest


def main():
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--n0", type=int, default=64)
    ap.add_argument("--levels", type=int, default=6)
    args = ap.parse_args()

    chart = regular_chart(args.n0, args.levels, boundary="reflect")
    n = chart.size
    true_rho = 0.04 * n
    icr = ICR(chart=chart, kernel=matern32.with_defaults(rho=true_rho),
              use_pallas=True)
    truth, obs_idx, y = charted_gp_dataset(
        icr, jax.random.PRNGKey(0), obs_frac=0.3, noise_std=0.05)
    print(f"N={n} points, {len(np.asarray(obs_idx))} noisy observations, "
          f"true rho={true_rho:.0f}")

    # every level must run fused — forward and backward (no jnp reference)
    for entry in dispatch.plan(chart):
        print(f"  level {entry['level']}: fwd={entry['route']} "
              f"bwd={entry['vjp']['route']} backend={entry['backend']}")
        assert entry["route"] != dispatch.ROUTE_REFERENCE, entry
        assert entry["vjp"]["route"] != dispatch.ROUTE_REFERENCE, entry

    # joint (field, theta) inference — matrices recomputed inside the step
    priors = StandardizedModel({"rho": lognormal_prior(0.06 * n, 0.03 * n)})
    ll = gaussian_log_likelihood(0.05, obs_idx)

    def fwd(latent):
        xi_s, xi_t = latent
        theta = dict(priors(xi_t))
        theta["sigma"] = 1.0
        return icr(xi_s, theta)

    latent0 = (icr.zero_xi(), priors.zero_xi())
    t0 = time.time()
    latent, losses = map_fit(ll, fwd, latent0, y, steps=args.steps, lr=2e-2)
    dt = time.time() - t0
    rec = np.asarray(fwd(latent).reshape(-1))
    rho_hat = float(priors(latent[1])["rho"])
    rmse = float(np.sqrt(np.mean((rec - np.asarray(truth)) ** 2)))
    print(f"MAP: {args.steps} steps in {dt:.1f}s "
          f"({dt/args.steps*1e3:.1f} ms/step)")
    print(f"  loss {float(losses[0]):.1f} -> {float(losses[-1]):.1f}")
    print(f"  field RMSE={rmse:.3f}  rho_hat={rho_hat:.0f} "
          f"(true {true_rho:.0f})")

    # uncertainties via mean-field ADVI over the field excitations
    mats = icr.matrices({"rho": rho_hat, "sigma": 1.0})
    fwd_field = lambda xi: icr.apply_sqrt(mats, xi)
    (mean, logstd), elbos = advi_fit(
        jax.random.PRNGKey(2), ll, fwd_field, latent[0], y,
        steps=max(args.steps // 2, 50))
    post_std = float(jnp.mean(jnp.exp(logstd[-1])))
    print(f"ADVI: ELBO {float(elbos[0]):.1f} -> {float(elbos[-1]):.1f}, "
          f"mean finest-level posterior std={post_std:.3f} (prior: 1.0)")

    # export the fit as a self-contained Posterior and serve it: posterior
    # field draws and MC predictive moments through the slab-packed GP
    # server (DESIGN.md §12) — the ADVI products no longer die here
    post = advi_posterior(icr, (mean, logstd),
                          theta={"rho": rho_hat, "sigma": 1.0})
    srv = GPFieldServer(post, slab=4)
    reqs = [GPRequest(kind="sample", n=2, seed=1),
            GPRequest(kind="moments", n=8, seed=2)]
    t0 = time.time()
    srv.run(reqs)
    dt = time.time() - t0
    assert all(r.done and r.error is None for r in reqs)
    mom = reqs[1]
    print(f"serve: {srv.rows_served} posterior draws in {srv.slabs_run} "
          f"slabs ({dt*1e3:.0f} ms, cache "
          f"{srv.cache_hits} hits/{srv.cache_misses} miss); "
          f"{len(reqs[0].fields)} fields + moments({mom.n}): "
          f"mean predictive std={float(np.mean(mom.std)):.3f}")


if __name__ == "__main__":
    main()
