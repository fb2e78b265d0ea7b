"""From a profiler trace to numbers: device busy time, the operations that
took most device time, and the longest idle gaps by what the host was doing.

`load_events` turns an .xplane.pb into plain tuples; everything after that
works on the tuples, so the reduction is checked on a small recorded excerpt
(benchmarks/tests/data/) without a chip.

A device plane's "XLA Ops" line nests: a `while` or `conditional` event
spans the events of its body.  Busy time is the union of the innermost
events, so the waits between the small operations inside a scanned segment
count as idle; an operation's time is its own, without what it contains.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
HOST_SPAN = "bench_segment"


def load_events(path: str):
    """[(plane, line, name, start_ns, duration_ns)] of an .xplane.pb."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def self_times(events):
    """[(name, start, end, self_ns, is_leaf)] of one line's events."""
    evs = sorted(((s, -(s + d), n, s + d) for (n, s, d) in events))
    out, stack = [], []  # stack entries: [name, start, end, child_ns, kids]

    def close(top):
        out.append((top[0], top[1], top[2], (top[2] - top[1]) - top[3],
                    top[4] == 0))

    for s, _neg, n, e in evs:
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
            stack[-1][4] += 1
        stack.append([n, s, e, 0.0, 0])
    while stack:
        close(stack.pop())
    return out


def union_ns(intervals):
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """[(start, end)] of the idle stretches of [lo, hi]."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return out


def reduce_events(events, chips: int):
    """The numbers the harness reports from one trace."""
    by_plane = defaultdict(list)
    host_spans = []
    for plane, line, name, s, d in events:
        if plane.startswith("/device:TPU:") and line == OPS_LINE:
            by_plane[plane].append((name, s, d))
        elif name == HOST_SPAN:
            host_spans.append((s, s + d))
    planes = sorted(by_plane)[:chips]
    if not planes:
        raise RuntimeError("the trace holds no TPU device plane with an "
                           f"{OPS_LINE!r} line")
    host_spans.sort()
    # the traced window: from the first to the last device event
    lo = min(s for p in planes for _, s, _ in by_plane[p])
    hi = max(s + d for p in planes for _, s, d in by_plane[p])
    busy, op_ns, gap_ns = [], defaultdict(float), defaultdict(float)
    for p in planes:
        st = self_times(by_plane[p])
        leaves = [(s, e) for _, s, e, _, leaf in st if leaf]
        busy.append(union_ns(leaves))
        for name, _s, _e, self_ns, _leaf in st:
            op_ns[name] += self_ns / len(planes)
        for s, e in gaps(leaves, lo, hi):
            mid = 0.5 * (s + e)
            inside = any(a <= mid <= b for a, b in host_spans)
            where = ("inside a dispatch (host waits on the device)" if inside
                     else "between dispatches (host loop)")
            gap_ns[where] += (e - s) / len(planes)
            gap_ns["longest " + where] = max(
                gap_ns["longest " + where], e - s)
    busy_s = sum(busy) / len(busy) / 1e9
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in top],
        "idle_gaps": [[n, t / 1e9] for n, t in
                      sorted(gap_ns.items(), key=lambda kv: -kv[1])],
    }


def reduce_dir(trace_dir: str, chips: int):
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return reduce_events(load_events(paths[0]), chips)
