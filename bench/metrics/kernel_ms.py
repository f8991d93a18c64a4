"""Device time of the refine_* kernels per served slab, in ms."""


def read(r):
    tr, c = r.get("trace"), r.get("counters")
    if not tr or tr["kernel_s"] <= 0 or not c or c["slabs"] <= 0:
        return None
    return 1e3 * tr["kernel_s"] / c["slabs"]
