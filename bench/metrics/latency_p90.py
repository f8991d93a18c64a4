"""Nearest-rank 90th percentile of the window's request latencies (host
clock, due time to completion; a missing answer counts as infinite)."""


def read(r):
    lat = r.get("latency")
    return None if not lat else lat["p90"]
