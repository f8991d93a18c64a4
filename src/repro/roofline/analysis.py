"""Roofline terms from a compiled (dry-run) executable.

Three terms, per device (equivalently: global / chips — the assignment's
formulae divide the global totals by chip count, which cancels because the
post-SPMD HLO module is already the per-device program):

  compute    = HLO_FLOPs / peak_FLOPs          [cost_analysis 'flops']
  memory     = HLO_bytes / HBM_bw              [cost_analysis 'bytes accessed']
  collective = collective_bytes / link_bw      [parsed from HLO text]

collective_bytes is NOT in cost_analysis: we parse the post-partitioning
HLO and sum operand sizes of every all-gather / all-reduce / reduce-scatter
/ all-to-all / collective-permute (ragged variants included). Shapes in the
SPMD module are local, so the sum is per-device traffic. all-reduce operands
are counted twice (reduce-scatter + all-gather phases of a ring).

The dominant term approximates the step's lower-bound time on the target
(the target chip's entry of the launch/mesh.py device table); the ratio MODEL_FLOPS/HLO_FLOPs
separates "useful" model math from remat/dispatch overhead.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

from repro.launch import mesh as hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16,
}

# f32[128,1024] / bf16[8]{0} / pred[] — first group dtype, second dims
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all",
)
# multiplier: ring all-reduce moves ~2x the payload (RS + AG phases)
_COLL_FACTOR = {"all-reduce": 2.0}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota form: [n_groups, group_size]<=[...]
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:  # explicit form: {{0,1,2,3},{...}} — size of the first group
        return max(len(m.group(1).split(",")), 1)
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective traffic from (post-SPMD) HLO text.

    Post-SPMD HLO prints operands without types, so operand sizes are
    reconstructed from the printed OUTPUT shape + op semantics + group size
    (ring traffic, up to the (g-1)/g factor):
      all-gather          out                (received payload = full array)
      reduce-scatter      out * g            (contributed payload = input)
      all-reduce          2 * out            (reduce-scatter + all-gather)
      all-to-all          out                (send == recv == array)
      collective-permute  out
    Async -start/-done pairs are counted once (at -start).
    """
    out: dict = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(
            r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([a-z\-]+?)(-start)?"
            r"(\.\d+)?\(", s)
        if not m:
            continue
        kind = m.group(2)
        if kind.endswith("-done") or kind not in _COLLECTIVES:
            continue
        shapes = _SHAPE_RE.findall(m.group(1))
        out_bytes = sum(_shape_bytes(d, dims) for d, dims in shapes)
        g = _group_size(s)
        if kind == "all-reduce":
            out_bytes *= 2
        elif kind == "reduce-scatter":
            out_bytes *= g
        out[kind] += int(out_bytes)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def collective_counts(hlo_text: str) -> dict:
    out: dict = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = re.match(
            r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*.+?\s+([a-z\-]+?)(-start)?"
            r"(\.\d+)?\(", line.strip())
        if m and m.group(1) in _COLLECTIVES:
            out[m.group(1)] += 1
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                 # per-device HLO flops (loop-aware)
    bytes_accessed: float        # per-device HBM traffic (loop-aware model)
    coll_bytes: float            # per-device collective traffic
    coll_breakdown: dict
    coll_counts: dict
    memory_per_device: Optional[dict] = None
    model_flops: float = 0.0     # 6·N·D (or 6·N_active·D) useful flops/device
    raw_xla: Optional[dict] = None  # uncorrected cost_analysis numbers

    @property
    def t_compute(self) -> float:
        return self.flops / hw.target_spec().peak_flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / hw.target_spec().hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / hw.target_spec().ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def flops_utilization(self) -> float:
        """Fraction of the roofline-bound step spent on useful model math
        (MODEL_FLOPS at peak): the dry-run analogue of MFU."""
        if self.bound_time == 0:
            return 0.0
        return (self.model_flops / hw.target_spec().peak_flops_bf16) / self.bound_time

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    def summary(self) -> dict:
        return {
            "flops": self.flops,
            "bytes": self.bytes_accessed,
            "coll_bytes": self.coll_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "bound_time_s": self.bound_time,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.flops_utilization,
            "coll_breakdown": {k: v for k, v in
                               self.coll_breakdown.items() if v},
            "coll_counts": {k: v for k, v in self.coll_counts.items() if v},
            "memory_per_device": self.memory_per_device,
            "raw_xla": self.raw_xla,
        }


def analyze_compiled(compiled, model_flops_per_device: float = 0.0
                     ) -> RooflineTerms:
    """Extract the three roofline terms from a jax Compiled object.

    Primary numbers come from the LOOP-AWARE HLO cost model (hlo_cost.py):
    ``compiled.cost_analysis()`` visits While bodies once, undercounting
    scanned programs by the trip count (5-60x here). The raw XLA numbers
    are kept in the summary for reference.
    """
    from .hlo_cost import module_costs

    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    text = compiled.as_text()
    la = module_costs(text)
    flops = float(la["flops"])
    bytes_accessed = float(la["bytes"])
    coll = dict(la["coll_by_kind"])
    coll["total"] = float(la["coll_bytes"])
    counts = la["coll_counts"]
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
                "generated_code_bytes": int(
                    getattr(ma, "generated_code_size_in_bytes", 0)),
            }
            # donated buffers alias input<->output (cache/params): counting
            # both sides would double-book them
            mem["total_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                                  + mem["temp_bytes"] - mem["alias_bytes"])
            mem["fits_hbm"] = mem["total_bytes"] <= hw.target_spec().hbm_bytes
    except Exception:
        pass
    terms = RooflineTerms(
        flops=flops, bytes_accessed=bytes_accessed,
        coll_bytes=float(coll["total"]), coll_breakdown=coll,
        coll_counts=counts, memory_per_device=mem,
        model_flops=model_flops_per_device,
    )
    terms.raw_xla = {"flops": float(cost.get("flops", 0.0)),
                     "bytes": float(cost.get("bytes accessed", 0.0))}
    return terms


def model_flops_train(n_params_active: int, tokens: int) -> float:
    """6·N·D (fwd 2ND + bwd 4ND) — global; divide by chips for per-device."""
    return 6.0 * n_params_active * tokens


def model_flops_decode(n_params_active: int, tokens: int) -> float:
    """2·N per generated token (fwd only)."""
    return 2.0 * n_params_active * tokens
