"""The whole served step's share of the chip's peak: the least time of
every slab's compulsory FLOPs and bytes (bench/counts.py) over the traced
window."""
import counts


def read(r):
    tr, c = r.get("trace"), r.get("counters")
    if not tr or tr["window_s"] <= 0 or not c or c["slabs"] <= 0 \
            or not r.get("peaks"):
        return None
    least = counts.least_seconds(r["work"]["step"], r["peaks"])
    return 100.0 * least * c["slabs"] / tr["window_s"]
