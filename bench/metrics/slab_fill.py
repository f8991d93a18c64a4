"""Rows served over slab capacity run: GPFieldServer.metrics() counters,
taken over the window."""


def read(r):
    c = r.get("counters")
    if not c or c["slabs"] <= 0:
        return None
    return 100.0 * c["rows"] / (c["slabs"] * c["capacity"])
