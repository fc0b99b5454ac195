"""Profiler trace -> device busy time, idle share, top operations, and the
idle gaps named by the harness span that was open in each.

Works on what ``phases.load`` reads from the ``.xplane.pb`` that
``jax.profiler`` writes: the ops of each device plane (``/device:*``;
from the ``XLA Ops`` line where it exists) and the spans of the host
plane on the same clock, of which the harness's own (``bench.*``, written
with ``jax.profiler.TraceAnnotation``) name the idle gaps.
"""

from __future__ import annotations

import glob
import os
import re

OP_LINE = "XLA Ops"
# One plane per chip: "/device:TPU:0"; planes such as a chip's
# "... SparseCore" or non-core planes carry no ops of the program.
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """An XLA op's name without its HLO text ("%fusion.12 = (...) ..." ->
    "fusion.12")."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def clip(intervals, t0, t1):
    """Intervals, (start, end) or ops (name, start, end, ...), cut to
    [t0, t1]; empty ones dropped."""
    out = []
    for iv in intervals:
        lo, hi = (iv[0], iv[1]) if len(iv) == 2 else (iv[1], iv[2])
        s, e = max(lo, t0), min(hi, t1)
        if e > s:
            out.append((s, e))
    return out


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals, t0, t1):
    """The stretches of [t0, t1] that no interval covers."""
    out, cur = [], t0
    for s, e in union(clip(intervals, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def attribute(gap_list, spans):
    """Name each gap by the innermost harness span open at its midpoint
    (``bench.other`` when none is)."""
    out = []
    for s, e in gap_list:
        mid = (s + e) / 2
        inside = [sp for sp in spans if sp[1] <= mid < sp[2]
                  and sp[0] != WINDOW_SPAN]
        name = min(inside, key=lambda sp: sp[2] - sp[1])[0] \
            if inside else "bench.other"
        out.append((name, (e - s) / 1e9))
    return out


def leaves(ops):
    """The ops that contain no other op: a while loop or conditional
    spans the ops of its body on the same line, and would hide them."""
    srt = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, op in enumerate(srt):
        nxt = srt[i + 1] if i + 1 < len(srt) else None
        if nxt is None or nxt[1] >= op[2]:
            out.append(op)
    return out


def reduce(devices, spans, top: int = 10) -> dict:
    """busy_s / window_s over the ``bench.window`` span, averaged over the
    device planes, plus the breakdown lists.  Ops may carry more fields
    after (name, start, end), as ``phases.load``'s do."""
    win = [sp for sp in spans if sp[0] == WINDOW_SPAN]
    if not win or not devices:
        return {}
    t0, t1 = win[0][1], win[0][2]
    busy, op_time, all_gaps = [], {}, []
    for ops in devices.values():
        iv = clip(ops, t0, t1)
        busy.append(busy_ns(iv) / 1e9)
        for name, s, e, *_ in leaves(ops):
            d = min(e, t1) - max(s, t0)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d / 1e9
        all_gaps += attribute(gaps(iv, t0, t1), spans)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(all_gaps, key=lambda g: -g[1])[:top]
    return {"busy_s": sum(busy) / len(busy), "window_s": (t1 - t0) / 1e9,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in top_gaps]}
