"""Quickstart: sample a GP with ICR and compare against the exact GP.

The 60-second tour of the paper: build a chart, pick a kernel, draw O(N)
GP samples with sqrt(K_ICR), and check the implied covariance against the
dense kernel matrix (only possible at small N — that's the point!).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax

from repro.core import (
    ICR,
    cov_errors,
    exact_cov,
    log_chart,
    matern32,
    regular_chart,
)


def main():
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    # --- 1. a GP on 200 log-spaced points (the paper's §5 setting) --------
    chart = log_chart(11, 5, n_csz=5, n_fsz=4, delta0=0.0197)
    n = chart.final_shape[0]
    xs = np.asarray(chart.grid_positions(chart.n_levels))[:, 0]
    rho = float(np.diff(xs).max())
    print(f"modeling {n} points; nearest-neighbor spacing spans "
          f"{np.diff(xs).min()/rho*100:.1f}%..100% of rho")

    icr = ICR(chart=chart, kernel=matern32.with_defaults(rho=rho))

    # --- 2. draw samples (O(N), no inversion, no log-det) ------------------
    mats = icr.matrices()          # refinement matrices (paper Eq. 7/8)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    samples = [icr.apply_sqrt(mats, icr.init_xi(k)) for k in keys]
    print("sample[0][:5] =", np.asarray(samples[0]).reshape(-1)[:5])

    # --- 3. validate the implied covariance against the exact kernel -------
    cov_icr = icr.implicit_cov(dtype=np.float32)
    cov_true = exact_cov(chart, matern32.with_defaults(rho=rho)())
    errs = {k: float(v) for k, v in cov_errors(cov_icr, cov_true).items()}
    print(f"covariance errors vs exact GP: MAE={errs['mae']:.2e} "
          f"(paper: 5.8e-3), max={errs['max_abs_err']:.2e} (paper: 0.13)")

    # --- 4. the same API scales: 1M-point regular chart ---------------------
    big = ICR(chart=regular_chart(1024, 10, boundary="reflect"),
              kernel=matern32.with_defaults(rho=5000.0))
    s = big.sample(jax.random.PRNGKey(1))
    print(f"1M-point sample: shape={s.shape}, std={float(s.std()):.3f} "
          "(same O(N) code path)")


if __name__ == "__main__":
    main()
