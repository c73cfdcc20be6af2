"""From the profiler's trace to device intervals.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``. A
chip is a plane named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation (the event's name is the instruction's
text), ``XLA Modules`` one per program call, ``Async XLA Ops`` the
copy-start/copy-done windows of asynchronous copies, which overlap the ops
and are not device work of their own. Host planes hold the benchmark's own
``TraceAnnotation`` spans. Times are nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)[-.\w]*\s*=|\b(all-reduce|all-gather|reduce-scatter"
    r"|all-to-all|collective-permute)(-start|-done)?\(")


# an op that only holds others (the scan's loop): its time is theirs
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*\s*(=|$)")


def short_name(text: str) -> str:
    """``%fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")[:80]


def union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] given merged busy intervals."""
    return subtract([(lo, hi)], clip(busy, lo, hi))


class Chip:
    """One device plane: ops as (start, end, name), module calls alike."""

    def __init__(self, name, ops, modules):
        self.name, self.ops, self.modules = name, ops, modules

    def window(self):
        """First module start to last module end: the traced window as the
        device saw it."""
        ev = self.modules or self.ops
        return (min(s for s, _, _ in ev), max(e for _, e, _ in ev))

    def busy(self):
        return union([(s, e) for s, e, _ in self.ops])

    def collectives(self):
        return union([(s, e) for s, e, n in self.ops if COLLECTIVE.search(n)])

    def compute(self):
        return union([(s, e) for s, e, n in self.ops
                      if not COLLECTIVE.search(n) and not CONTAINER.match(n)])


def chips_from_events(planes) -> list:
    """``planes``: {plane name: {line name: [(start_ns, dur_ns, name)]}}."""
    out = []
    for pname in sorted(planes):
        if not pname.startswith("/device:TPU:"):
            continue
        lines = planes[pname]
        conv = lambda evs: [(s, s + d, n) for s, d, n in evs]
        out.append(Chip(pname, conv(lines.get(OPS_LINE, [])),
                        conv(lines.get(MODULES_LINE, []))))
    return out


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_xplane(path, host_prefix="perfbench_"):
    """The device planes' op and module events, and host spans whose name
    starts with ``host_prefix``, as ``chips_from_events`` takes them."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = planes.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (ev.start_ns, ev.duration_ns, ev.name)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
    return planes, spans


def reduce_chips(chips) -> dict:
    """What the readers and the result line need, averaged over chips:
    busy and window seconds, idle share, the share of the window in which
    only a collective ran, the ten operations that took most time and the
    ten longest idle gaps with the operation that ended each."""
    if not chips or not any(c.ops for c in chips):
        return {}
    busy_s = window_s = exposed_s = coll_s = 0.0
    ops_time, gap_list = {}, []
    for c in chips:
        lo, hi = c.window()
        busy = clip(c.busy(), lo, hi)
        busy_s += total(busy) / 1e9
        window_s += (hi - lo) / 1e9
        coll = clip(c.collectives(), lo, hi)
        coll_s += total(coll) / 1e9
        exposed_s += total(subtract(coll, c.compute())) / 1e9
        for s, e, n in c.ops:
            if CONTAINER.match(n):
                continue
            k = short_name(n)
            ops_time[k] = ops_time.get(k, 0.0) + (e - s) / 1e9 / len(chips)
        for s, e in gaps(busy, lo, hi):
            gap_list.append(((e - s) / 1e9, s))
    n = len(chips)
    top_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    gap_list.sort(reverse=True)
    return {
        "busy_s": busy_s / n, "window_s": window_s / n,
        "idle_share": 1.0 - busy_s / window_s,
        "collective_s": coll_s / n, "exposed_collective_s": exposed_s / n,
        "device_ops": [[k, v] for k, v in top_ops],
        "gaps": gap_list[:10],
        "gap_total_s": sum(g for g, _ in gap_list) / n,
        "gap_count": len(gap_list) / n,
    }


def name_gaps(gap_list, spans):
    """[[what the host was doing, seconds]] for idle gaps: the benchmark's
    own innermost annotation over the gap's start, else 'unattributed'."""
    out = []
    for dur, start in gap_list:
        inside = [(e - s, n) for s, e, n in spans if s <= start < e]
        out.append([min(inside)[1] if inside else "unattributed", dur])
    return out
