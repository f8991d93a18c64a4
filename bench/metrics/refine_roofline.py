"""The refinement kernels' share of their roofline: the least time of
every slab's refinement work (from chart geometry: bench/counts.py) over
the device time of the refine_* kernel events."""
import counts


def read(r):
    tr, c = r.get("trace"), r.get("counters")
    if not tr or tr["kernel_s"] <= 0 or not c or c["slabs"] <= 0 \
            or not r.get("peaks"):
        return None
    least = counts.least_seconds(r["work"]["refine"], r["peaks"])
    return 100.0 * least * c["slabs"] / tr["kernel_s"]
