"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy and idle time over the measured window, device time by
operation name, collective time per device, and the idle gaps labelled by
what the host was doing.

The measured window is the host span named ``window`` that the harness
writes around it; device operations are clipped to it.  Device operations
are the events of the ``XLA Ops`` line of each ``/device:`` plane.  A trace
recorded on the CPU has no such plane: there the operations are the host
events that carry an ``hlo_op`` stat, grouped by their ``device_ordinal``
(this is what the recorded test trace exercises; the harness itself never
runs off the chip).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

WINDOW_SPAN = "window"
COLLECTIVE = re.compile(
    r"all[-_]?reduce|all[-_]?gather|reduce[-_]scatter|collective[-_]permute"
    r"|all[-_]to[-_]all|psum",
    re.IGNORECASE,
)


class TraceSummary(NamedTuple):
    window_s: float                  # length of the measured window
    busy_s: dict                     # device -> union of op intervals (s)
    op_s: dict                       # op name -> device self seconds, all devices
    op_text: dict                    # op name -> name and string stats
    collective_s: dict               # device -> collective op seconds
    idle_gaps: dict                  # host label -> idle seconds, mean over devices

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def kernel_s(self, names) -> float | None:
        """Device seconds of every op whose name or string stats hold one
        of ``names``; None where no such op ran."""
        hits = [s for op, s in self.op_s.items()
                if any(n in self.op_text[op] for n in names)]
        return sum(hits) if hits else None


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _host_line(planes):
    """The host thread that holds the ``window`` span, and its events as
    ``(name, start_ns, end_ns)``."""
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(n == WINDOW_SPAN for n, _, _ in evs):
                return line.name, evs
    return None, []


def _short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.12 = f32[...] ...`` is
    ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def _self_times(evs):
    """``[(name, text, start, end, self_ns), ...]``: an op that holds others
    (a ``while`` around its body's ops) keeps only the time none of them
    covers, so each instant of device time counts once."""
    out = []
    stack = []  # indices into out of the ops still open
    for name, text, s, e in sorted(evs, key=lambda ev: (ev[2], -ev[3])):
        while stack and out[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[4] -= min(e, parent[3]) - s
        out.append([name, text, s, e, e - s])
        stack.append(len(out) - 1)
    return out


def _device_ops(planes, host_line_name):
    """``{device: [(name, text, start_ns, end_ns), ...]}``."""
    ops = defaultdict(list)
    device_planes = [p for p in planes if p.name.startswith("/device:")]
    for plane in device_planes:
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                text = " ".join([e.name[:400]] + [str(v)[:200] for _, v in e.stats
                                                  if isinstance(v, str)])
                ops[plane.name].append(
                    (_short(e.name), text, e.start_ns, e.start_ns + e.duration_ns))
    if device_planes:
        return ops
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if line.name == host_line_name:
                continue
            for e in line.events:
                st = _stats(e)
                if "hlo_op" not in st or e.duration_ns <= 0:
                    continue
                dev = f"cpu:{st.get('device_ordinal', 0)}"
                text = " ".join(str(v) for v in (e.name, st.get("hlo_op"),
                                                 st.get("hlo_module")))
                ops[dev].append(
                    (e.name, text, e.start_ns, e.start_ns + e.duration_ns))
    return ops


def _label(starts, host_events, t):
    """Innermost host event around time ``t`` (other than the window):
    with nested spans, the covering event that started last."""
    i = bisect.bisect_right(starts, t) - 1
    for name, s, e in reversed(host_events[max(i - 2000, 0):i + 1]):
        if name != WINDOW_SPAN and s <= t <= e:
            return name
    return "outside spans"


def reduce_trace(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    host_name, host_events = _host_line(planes)
    windows = [(s, e) for n, s, e in host_events if n == WINDOW_SPAN]
    ops = _device_ops(planes, host_name)
    if windows:
        lo, hi = windows[0]
    else:
        every = [(s, e) for dev in ops.values() for _, _, s, e in dev]
        lo = min(s for s, _ in every)
        hi = max(e for _, e in every)
    spans = sorted((ev for ev in host_events if ev[2] > lo and ev[1] < hi),
                   key=lambda ev: ev[1])
    starts = [s for _, s, _ in spans]

    busy, coll = {}, {}
    op_s, op_text = defaultdict(float), {}
    gaps = defaultdict(float)
    for dev, evs in ops.items():
        clipped = []
        c = 0.0
        inside = [(n, t, max(s, lo), min(e, hi)) for n, t, s, e in evs
                  if min(e, hi) > max(s, lo)]
        for name, text, s, e, self_ns in _self_times(inside):
            clipped.append((s, e))
            op_s[name] += self_ns * 1e-9
            op_text.setdefault(name, text)
            if COLLECTIVE.search(name):
                c += self_ns * 1e-9
        merged = _union(clipped)
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        coll[dev] = c
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps[_label(starts, spans, 0.5 * (prev + s))] += (s - prev) * 1e-9
            prev = max(prev, e)
    n_dev = max(len(ops), 1)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy,
        op_s=dict(op_s),
        op_text=op_text,
        collective_s=coll,
        idle_gaps={k: v / n_dev for k, v in gaps.items()},
    )


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The result line's ``breakdown``: device ops that took most time, and
    idle time by what the host was doing (each list at most ``top`` long)."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
