"""Device time of every other op per served slab, in ms: level 0, the
draw of the excitations, the phase-plane relayouts and the interleave of
each level's children into the field."""


def read(r):
    tr, c = r.get("trace"), r.get("counters")
    if not tr or tr["other_s"] <= 0 or not c or c["slabs"] <= 0:
        return None
    return 1e3 * tr["other_s"] / c["slabs"]
