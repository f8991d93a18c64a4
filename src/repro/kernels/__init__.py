"""Pallas TPU kernels for the ICR refinement hot-spot.

  launch.py     — declarative LaunchPlan records every kernel launches
                  through (DESIGN.md §14) and the verifier analyzes
  planes.py     — phase-plane tiles and the shared in-kernel contraction
                  step every kernel body runs (DESIGN.md §3)
  icr_refine.py — pl.pallas_call kernels (stationary + charted variants),
                  forward AND adjoint, glued by jax.custom_vjp
  nd.py         — fused N-D refinement as per-axis 1-D passes
  nd_fused.py   — single-launch fused N-D level megakernel
  pyramid.py    — VMEM-resident multi-level launch (DESIGN.md §11)
  policy.py     — storage/accumulation dtype policy (DESIGN.md §11)
  dispatch.py   — per-level backend/route selection + VMEM autotune +
                  launch-plan export (level_launch_plans / chart_launch_plans)
  ref.py        — pure-jnp oracles the kernels are validated against
"""
from . import dispatch, launch, nd, policy, pyramid, ref
from .icr_refine import (
    refine_charted_adjoint_pallas,
    refine_charted_pallas,
    refine_stationary_adjoint_pallas,
    refine_stationary_pallas,
)
from .launch import IndexMap, LaunchPlan, OperandSpec, PlanMismatchError
from .nd import refine_axes
from .policy import BF16, FP32, DtypePolicy
from .pyramid import refine_pyramid

__all__ = [
    "dispatch", "launch", "nd", "policy", "pyramid", "ref",
    "refine_stationary_pallas", "refine_charted_pallas", "refine_axes",
    "refine_stationary_adjoint_pallas", "refine_charted_adjoint_pallas",
    "refine_pyramid", "DtypePolicy", "BF16", "FP32",
    "IndexMap", "OperandSpec", "LaunchPlan", "PlanMismatchError",
]
