"""Profiler capture and its reduction to device busy time, kernel time and
idle gaps.

A traced run wraps its window in the host span ``bench.window`` and each
call into the program in a ``bench.*`` span (``jax.profiler
.TraceAnnotation``), so device time and host spans share one clock. The
reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:
device planes are ``/device:TPU:<n>``, their op events the ``XLA Ops``
line; busy time is the union of op intervals inside the window.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil

KERNEL_PREFIX = "refine_"  # every Pallas refinement kernel's name
WINDOW = "bench.window"
TOP = 10


class Tracer:
    """Profiler on or off for one run; ``span`` names host work either way."""

    def __init__(self, enabled: bool, outdir: str):
        self.enabled, self.outdir = enabled, outdir

    @contextlib.contextmanager
    def capture(self):
        if not self.enabled:
            yield
            return
        import jax

        shutil.rmtree(self.outdir, ignore_errors=True)
        os.makedirs(self.outdir)
        jax.profiler.start_trace(self.outdir)
        try:
            with self.span(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def events(self) -> dict:
        """The capture as plain lists, then the capture removed."""
        files = glob.glob(os.path.join(self.outdir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        out = extract(files[0])
        shutil.rmtree(self.outdir, ignore_errors=True)
        return out


def op_name(text: str) -> str:
    """An op event's name is its HLO instruction (``%refine_pyramid.1 =
    f32[...] custom-call(...)``); the instruction's own name is kept."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_kernel(text: str) -> bool:
    """A Pallas refinement kernel: a TPU custom call named ``refine_*``."""
    return (op_name(text).startswith(KERNEL_PREFIX)
            and "tpu_custom_call" in text)


def extract(path: str) -> dict:
    """``{"ops": {plane: [[name, start_ns, dur_ns, is_kernel], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}`` from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" \
                not in plane.name:
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    evs.append([op_name(ev.name), float(ev.start_ns),
                                float(ev.duration_ns), is_kernel(ev.name)])
            ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
    return {"ops": ops, "spans": spans}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


CONTROL_FLOW = ("while", "conditional", "call")


def leaves(evs: list) -> list:
    """The ops that do work themselves: an HLO ``while``, ``conditional``
    or ``call`` (a scan's loop) spans its body's ops on the same trace
    line, and counting both would count the body twice."""
    return [e for e in evs if e[0].split(".")[0] not in CONTROL_FLOW]


def reduce(events: dict) -> dict:
    """Busy and window seconds, kernel and other device seconds, the ops
    that took most time and the longest idle gaps, named by the host span
    that covered them. Only leaf ops count; times are summed over chips,
    busy averaged."""
    win = [s for s in events["spans"] if s[0] == WINDOW]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(win)}")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    spans = sorted((s for s in events["spans"] if s[0] != WINDOW),
                   key=lambda s: s[2])  # innermost (shortest) first
    busy, kernel, other, by_name, gaps = 0.0, 0.0, 0.0, {}, []
    planes = [p for p, evs in events["ops"].items() if evs]
    for plane in planes:
        ivs = []
        for name, s, d, kern in leaves(events["ops"][plane]):
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 <= s0:
                continue
            ivs.append((s0, s1))
            dt = (s1 - s0) * 1e-9
            if kern:
                kernel += dt
            else:
                other += dt
            by_name[name] = by_name.get(name, 0.0) + dt
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                mid = 0.5 * (g0 + g1)
                host = next((s[0] for s in spans
                             if s[1] <= mid <= s[1] + s[2]), "no span")
                gaps.append([host, (g1 - g0) * 1e-9])
    n = max(1, len(planes))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gaps, key=lambda g: -g[1])[:TOP]
    return {"busy_s": busy / n, "window_s": (w1 - w0) * 1e-9,
            "kernel_s": kernel, "other_s": other, "chips": n,
            "device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}
