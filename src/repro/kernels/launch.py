"""Declarative launch plans for every Pallas kernel in the repo.

A :class:`LaunchPlan` is a pure-static record of one ``pallas_call``:
the grid, every input/output operand with its block shape, *named*
index map, full (padded) array shape and dtype, plus the accumulation
dtype and the tile parameters the kernel was specialized with.

The plan is the single source of truth for the launch geometry — the
kernel impls in ``icr_refine.py`` / ``nd_fused.py`` / ``pyramid.py``
build a plan first and then hand it to :func:`run_plan`, which
constructs the actual ``pallas_call`` from the plan (after asserting
the concrete operands match the plan's array shapes).  The same plan
objects are exported through ``dispatch.level_launch_plans`` /
``dispatch.chart_launch_plans`` so ``analysis/kernel_verify.py`` can
*prove* properties about the launch (exact output coverage, in-bounds
halo reads, VMEM working-set bytes) without running the kernel.

Halo-overlapped operands are modeled explicitly: the *main* view
carries an ``overhang`` — per-dimension ``(lo, hi)`` element counts it
needs beyond its own block — and each shifted *halo* view names the
main view via ``halo_of``.  The verifier checks that the union of the
blocks fetched by the group covers the overhang at every grid step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.launch.mesh import target_spec

# Scoped-VMEM limit handed to Mosaic for every compiled launch. The
# autotuners budget half of the target chip's VMEM for their working-set
# models (dispatch.VMEM_BUDGET_BYTES); the limit grants three quarters, so
# a tile the autotuner picks is never refused and the compiler keeps room
# for its own spills.
VMEM_LIMIT_BYTES = target_spec().vmem_bytes * 3 // 4


def padded_block_elems(block_shape, itemsize: int) -> int:
    """Elements a block occupies in VMEM: Mosaic tiles the last two dims
    by (sublanes, 128) with 8 sublanes of 32-bit words (16 for 16-bit)."""
    shape = tuple(block_shape) or (1,)
    if len(shape) == 1:
        shape = (1,) + shape
    sub = 8 * max(1, 4 // itemsize)
    rows = -(-shape[-2] // sub) * sub
    lanes = -(-shape[-1] // 128) * 128
    return math.prod(shape[:-2]) * rows * lanes


@dataclasses.dataclass(frozen=True)
class IndexMap:
    """A BlockSpec index map with a printable name.

    ``name`` is the human-readable form used in verifier findings and
    plan descriptions (e.g. ``"(b, i + 1)"``); ``fn`` is the actual
    callable handed to ``pl.BlockSpec`` — it takes the grid indices and
    returns *block* indices (Pallas multiplies by the block shape).
    """

    name: str
    fn: Callable[..., Tuple[int, ...]]

    def __call__(self, *grid_idx):
        return self.fn(*grid_idx)


@dataclasses.dataclass(frozen=True)
class OperandSpec:
    """One input or output operand of a planned launch.

    ``array_shape`` is the shape of the concrete (padded) array passed
    to ``pallas_call`` — not the logical pre-padding shape.  For
    halo-overlapped reads, the main view sets ``overhang`` (per-dim
    ``(lo, hi)`` extra elements the kernel consumes beyond the view's
    own block) and each shifted sibling sets ``halo_of`` to the main
    view's name; siblings alias the same concrete array.
    """

    name: str
    block_shape: Tuple[int, ...]
    index_map: IndexMap
    array_shape: Tuple[int, ...]
    dtype: str
    halo_of: Optional[str] = None
    overhang: Optional[Tuple[Tuple[int, int], ...]] = None
    # "smem": a small whole-array operand the kernel reads as scalars
    # (the stationary stencils), resident in scalar memory, never tiled
    memory_space: str = "vmem"

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def block_bytes(self) -> int:
        """VMEM bytes of one block, tile padding included."""
        if self.memory_space == "smem":
            return 0
        return padded_block_elems(self.block_shape, self.itemsize) \
            * self.itemsize

    @property
    def array_bytes(self) -> int:
        return math.prod(self.array_shape) * self.itemsize

    def block_spec(self) -> pl.BlockSpec:
        if self.memory_space == "smem":
            return pl.BlockSpec(memory_space=pltpu.SMEM)
        return pl.BlockSpec(self.block_shape, self.index_map.fn)


def smem_scalars(name: str, n: int) -> "OperandSpec":
    """Whole-array float32 scalar table in SMEM (``n`` entries)."""
    zeros = (0,)
    return OperandSpec(name, (n,), IndexMap("(0)", lambda *g: zeros),
                       (n,), "float32", memory_space="smem")


@dataclasses.dataclass(frozen=True)
class LaunchPlan:
    """A complete, statically analyzable description of one pallas_call."""

    kernel: str
    grid: Tuple[int, ...]
    inputs: Tuple[OperandSpec, ...]
    outputs: Tuple[OperandSpec, ...]
    accum_dtype: str
    params: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def grid_size(self) -> int:
        return math.prod(self.grid)

    def operand(self, name: str) -> OperandSpec:
        for op in (*self.inputs, *self.outputs):
            if op.name == name:
                return op
        raise KeyError(name)

    def block_bytes(self) -> int:
        """Double-buffered VMEM working set implied by the plan."""
        return 2 * sum(op.block_bytes for op in (*self.inputs, *self.outputs))

    def describe(self) -> dict:
        """JSON-safe plain-dict form for fingerprints / CLI output."""
        def op_desc(op):
            d = {"name": op.name, "block_shape": list(op.block_shape),
                 "index_map": op.index_map.name,
                 "array_shape": list(op.array_shape), "dtype": op.dtype}
            if op.halo_of:
                d["halo_of"] = op.halo_of
            if op.overhang:
                d["overhang"] = [list(p) for p in op.overhang]
            if op.memory_space != "vmem":
                d["memory_space"] = op.memory_space
            return d

        return {
            "kernel": self.kernel,
            "grid": list(self.grid),
            "accum_dtype": self.accum_dtype,
            "inputs": [op_desc(op) for op in self.inputs],
            "outputs": [op_desc(op) for op in self.outputs],
            "params": {k: (list(v) if isinstance(v, tuple) else v)
                       for k, v in dict(self.params).items()},
        }


def pad_to(arr, shape):
    """Zero-pad ``arr`` (trailing pad per dim) up to a plan's array shape."""
    pads = [(0, sz - cur) for cur, sz in zip(arr.shape, shape)]
    if any(hi for _lo, hi in pads):
        arr = jnp.pad(arr, pads)
    return arr


class PlanMismatchError(ValueError):
    """A concrete operand does not match the plan that claims to launch it."""


def run_plan(kern, plan: LaunchPlan, operands, *, interpret: bool):
    """Build and invoke the ``pallas_call`` described by ``plan``.

    The plan IS the launch: grid, BlockSpecs and out_shape are all
    constructed from the plan record, and every concrete operand is
    checked against the plan's array shapes/dtypes first — so the
    geometry the verifier analyzed is exactly the geometry that runs.
    """
    if len(operands) != len(plan.inputs):
        raise PlanMismatchError(
            f"{plan.kernel}: plan has {len(plan.inputs)} inputs, "
            f"got {len(operands)} operands")
    for arr, op in zip(operands, plan.inputs):
        if tuple(arr.shape) != op.array_shape:
            raise PlanMismatchError(
                f"{plan.kernel}: operand {op.name!r} has shape "
                f"{tuple(arr.shape)}, plan says {op.array_shape}")
        if jnp.dtype(arr.dtype) != jnp.dtype(op.dtype):
            raise PlanMismatchError(
                f"{plan.kernel}: operand {op.name!r} has dtype "
                f"{jnp.dtype(arr.dtype).name}, plan says {op.dtype}")

    in_specs = [op.block_spec() for op in plan.inputs]
    out_specs = [op.block_spec() for op in plan.outputs]
    out_shape = [jax.ShapeDtypeStruct(op.array_shape, jnp.dtype(op.dtype))
                 for op in plan.outputs]
    single = len(plan.outputs) == 1
    call = pl.pallas_call(
        kern,
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=out_specs[0] if single else out_specs,
        out_shape=out_shape[0] if single else out_shape,
        interpret=interpret,
        compiler_params=(None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES)),
        name=plan.kernel,
    )
    return call(*operands)
