"""The paper's flagship application, scaled to a laptop: a 3-D 'dust map'
GP on a (log-r, u, v) chart (paper §6, ref [24] — the 122-billion-DOF run).

Radial axis charted (per-pixel refinement matrices), angular axes
translation-invariant (matrices broadcast — the §4.3 symmetry trick). With
``use_pallas=True`` every refinement level runs through the fused N-D
kernel path (DESIGN.md §4–5): Pallas on TPU; off-TPU the production
backend executes the jnp oracle of the same fused structure
(``REPRO_BACKEND=interpret`` emulates the exact kernel tiling instead) —
the *routing* never falls back to the unstructured joint reference.
The same DistributedICR used here runs the 512-chip dry-run cell
``icr-dust122b`` (launch/dryrun.py).

Run:  PYTHONPATH=src python examples/dust_map_3d.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import ICR, matern32
from repro.core.charts import galactic_dust_chart
from repro.core.distributed import DistributedICR
from repro.compat import use_mesh
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh


def main():
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    chart = galactic_dust_chart((8, 16, 16), n_levels=3)
    icr = ICR(chart=chart, kernel=matern32.with_defaults(rho=0.5),
              use_pallas=True)
    shape = chart.final_shape
    print(f"dust chart: {shape} = {np.prod(shape):,} voxels, "
          f"{chart.n_levels} refinement levels")
    print("radial spacings (kpc-ish):",
          np.round(np.diff(np.exp(chart.axis_coords(chart.n_levels, 0)))[:5],
                   4))

    # the first two levels fit VMEM together: they run as ONE pyramid
    # launch (DESIGN.md §11), their intermediate field never touches HBM;
    # the third runs the single-launch megakernel. The plan reports the
    # position-aware bytes at both storage dtypes (bf16 must halve every
    # estimate).
    plan = dispatch.plan(chart)
    plan16 = dispatch.plan(chart, dtype="bfloat16")
    for entry, e16 in zip(plan, plan16):
        hb, hb16 = entry["hbm_bytes"], e16["hbm_bytes"]
        print(f"  level {entry['level']}: route={entry['route']} "
              f"backend={entry['backend']} vjp={entry['vjp']['route']} "
              f"est HBM {hb['selected']/1e6:.2f} MB f32 / "
              f"{hb16['selected']/1e6:.2f} MB bf16 "
              f"({hb['nd-axes']/hb['selected']:.1f}x less than per-axis)")
        want = (dispatch.ROUTE_PYRAMID if entry["level"] < 2
                else dispatch.ROUTE_ND_FUSED)
        assert entry["route"] == want, (
            "dust-map level fell off its fused route", entry)
        assert hb["selected"] >= 1.9 * hb16["selected"], (hb, hb16)

    # the per-level view underneath (what runs with use_pyramid=False):
    # the single-launch megakernel — never the jnp reference
    for entry in dispatch.plan(chart, pyramid=False):
        assert entry["route"] == dispatch.ROUTE_ND_FUSED, (
            "dust-map level fell off the megakernel route", entry)
        assert entry["vjp"]["route"] != dispatch.ROUTE_REFERENCE, (
            "fused backward fell back", entry)

    # single-device sample through the fused kernels
    sample = icr.sample(jax.random.PRNGKey(0))
    print(f"sample: shape={sample.shape} mean={float(sample.mean()):+.3f} "
          f"std={float(sample.std()):.3f}")

    # the same model under the mixed-precision policy (DESIGN.md §11):
    # bf16 storage + f32 accumulation — half the HBM bytes per level.
    # Same excitation values (cast), so the two fields are comparable.
    icr16 = ICR(chart=chart, kernel=matern32.with_defaults(rho=0.5),
                use_pallas=True, dtype_policy="bf16")
    xi = icr.init_xi(jax.random.PRNGKey(0))
    s32 = icr.apply_sqrt(icr.matrices(), xi)
    s16 = icr16.apply_sqrt(icr16.matrices(),
                           [x.astype(jnp.bfloat16) for x in xi])
    rel = float(jnp.abs(s16.astype(jnp.float32) - s32).max()
                / jnp.abs(s32).max())
    print(f"bf16 sample: dtype={s16.dtype} rel-err vs f32 {rel:.3f} "
          "(bf16 rounding, fp32 accumulation)")
    assert s16.dtype == jnp.bfloat16 and rel < 0.05


    # one inference-style gradient through the fused path: MAP/ADVI cost is
    # two sqrt applications + the VJP (paper §1) — all adjoint kernels here
    # (demoed on a half-size chart: interpret mode off-TPU pays emulation
    # overhead per launch, and the example must stay laptop-sized)
    small = galactic_dust_chart((6, 8, 8), n_levels=2)
    icr_s = ICR(chart=small, kernel=matern32.with_defaults(rho=0.5),
                use_pallas=True)
    mats = icr_s.matrices()
    xi = icr_s.init_xi(jax.random.PRNGKey(1))
    grad = jax.grad(
        lambda xs: 0.5 * jnp.sum(icr_s.apply_sqrt(mats, xs) ** 2))(xi)
    gnorm = float(sum(jnp.sum(g**2) for g in grad)) ** 0.5
    print(f"fused VJP: |d loss/d xi| over {len(grad)} levels = {gnorm:.2f}")
    # Wiener-filter-style transpose diagnostics share the same adjoints
    back = icr_s.apply_sqrt_T(mats, icr_s.sample(jax.random.PRNGKey(2)))
    print(f"sqrt(K)^T residual map: level sizes = {[b.size for b in back]}")

    # batched posterior-style sampling: the sample batch rides natively
    # inside the kernel tiles (matrices fetched once per tile slab) instead
    # of looping — the serving fast path (demoed on the half-size chart;
    # interpret mode pays emulation overhead per launch)
    batch = icr_s.sample_batch(jax.random.PRNGKey(42), 3)
    print(f"sample_batch(3): shape={batch.shape} "
          f"per-sample std={[round(float(b.std()), 3) for b in batch]}")

    # distributed sample across every local device (spatial ring over the
    # middle angular axis — halo exchange via collective_permute)
    n_dev = len(jax.devices())
    if n_dev > 1:
        mesh = make_mesh((n_dev,), ("space",))
        dist = DistributedICR(icr=icr, mesh=mesh, axis_names=("space",),
                              shard_axis=1)
        with use_mesh(mesh):
            s2 = dist.sample(jax.random.PRNGKey(0))
        print(f"distributed over {n_dev} devices: shape={s2.shape}, "
              "sharded along the angular axis")
    else:
        print("(1 device visible — run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 to see "
              "the halo-exchange path)")

    # radial correlation structure: nearby shells correlate strongly
    v = np.asarray(sample)
    c01 = np.corrcoef(v[0].ravel(), v[1].ravel())[0, 1]
    c0n = np.corrcoef(v[0].ravel(), v[-1].ravel())[0, 1]
    print(f"corr(shell0, shell1)={c01:.2f}  corr(shell0, shell-1)={c0n:.2f} "
          "(decaying with distance, as the Matern kernel dictates)")


if __name__ == "__main__":
    main()
