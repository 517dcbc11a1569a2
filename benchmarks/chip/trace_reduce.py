"""Reduce a `jax.profiler` trace (`.xplane.pb`) to what the per-layer
metrics read: device busy time as the union of operation intervals,
device time per operation name, and the device's idle gaps, each named
for the harness host span open over most of it.

Device planes are those named `/device:...` that carry an operation
line (`XLA Ops`); host spans are the harness's `TraceAnnotation` events
on the host plane.  Times are clipped to the traced window: the host
span named `window`.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
WINDOW = "window"
# ops whose interval holds other ops of the same line: they count toward
# busy time, but not toward any op's own time
NESTING = ("while", "conditional", "call")


@dataclasses.dataclass
class Summary:
    window_s: float                      # length of the traced window
    busy_s: float                        # union of op intervals, mean
    #                                      over the devices
    op_s: dict                           # op base name -> device s
    op_count: dict                       # op base name -> events
    gaps: list                           # [(span name, seconds)] per gap
    devices: int

    custom: set                          # base names of custom calls

    def kernels(self, names):
        """Base names of the custom calls (Pallas kernels) of `names`.
        The compiler names a kernel's call for the function that makes
        it, wrapped by where it is traced: `masked_matmul_dx` in a
        train step, `transpose_jvp_jit_masked_matmul_dx___` alone."""
        pats = [re.compile(rf"(?:^|_){re.escape(k)}_*$") for k in names]
        return [b for b in self.custom if any(p.search(b) for p in pats)]

    def kernel_s(self, names) -> float:
        return sum(self.op_s[b] for b in self.kernels(names))

    def kernel_count(self, names) -> int:
        return sum(self.op_count[b] for b in self.kernels(names))

    def gap_by_span(self):
        tot = collections.Counter()
        for name, s in self.gaps:
            tot[name] += s
        return tot


def base_name(op: str) -> str:
    """The HLO instruction's name without the compiler's numbering:
    "%masked_matmul_dx.79 = bf16[...] custom-call(...)" and
    "masked_matmul_dx.79" both give "masked_matmul_dx"."""
    name = op.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"(\.\d+)+$", "", name)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Merge (start, end) intervals; returns the merged sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(path: str, spans=()) -> Summary:
    """Summarize the trace at `path` (a file or a profiler directory).
    `spans` are the host span names to attribute gaps to."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    host, dev_ops = [], []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/host:"):
            for line in lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name in spans:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [l for l in lines if l.name == OPS_LINE]
            if ops:
                dev_ops.append([(ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns)
                                for ev in ops[0].events])
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError("the trace holds no `window` host span")
    w0, w1 = wins[0]
    if not dev_ops:
        raise ValueError("the trace holds no device operations")
    op_s, op_count = collections.Counter(), collections.Counter()
    custom = set()
    busy, gaps = [], []
    for k, ops in enumerate(dev_ops):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        for n, s, e in clipped:
            b = base_name(n)
            if b not in NESTING:
                op_s[b] += (e - s) * 1e-9
                op_count[b] += 1
                if "custom-call(" in n:
                    custom.add(b)
        merged = _union([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if k == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = _name_gaps(host, [(g0, g1) for g0, g1 in
                                     zip(edges[::2], edges[1::2])
                                     if g1 > g0])
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / len(busy), op_s=dict(op_s),
                   op_count=dict(op_count), gaps=gaps,
                   devices=len(dev_ops), custom=custom)


def _name_gaps(host, gaps):
    """[(name of the span over most of the gap, seconds)] for sorted,
    disjoint gaps; spans other than `window` follow one another."""
    spans = sorted((s, e, n) for n, s, e in host if n != WINDOW)
    out, i = [], 0
    for g0, g1 in gaps:
        while i < len(spans) and spans[i][1] <= g0:
            i += 1
        best, name, j = 0.0, "other", i
        while j < len(spans) and spans[j][0] < g1:
            o = _overlap(g0, g1, spans[j][0], spans[j][1])
            if o > best:
                best, name = o, spans[j][2]
            j += 1
        out.append((name, (g1 - g0) * 1e-9))
    return out


def breakdown(summary: Summary, top: int = 10):
    """The `breakdown` of a traced run's result line: the device ops that
    took most time, and the idle time by host span."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.gap_by_span().items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}
