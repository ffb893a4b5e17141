"""Reduction of a profiler trace to device busy time, top ops and idle gaps.

The harness wraps the traced slice of its window in one host span
(``SLICE``); everything is measured inside that span's interval. Busy
time is the union of the intervals of the device's ops (the ``XLA Ops``
line of each ``/device:`` plane), so nested or overlapping ops count
once. Idle gaps are the holes in that union, each named by what the host
was doing at its middle: the innermost event of the host thread that
holds the harness's spans, under the harness span around it.

The top ops count leaf ops only (a ``while`` op holds the ops of its
body on the same line), under the HLO instruction's name, the part of
the trace name before `` = ``. The device's clock in the trace may sit
about a millisecond off the host's, so a gap shorter than that can be
named by the host's work beside it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Any

SLICE = "chipbench.traced_slice"
HARNESS_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
TOP = 10


def load(trace_dir: str) -> Any:
    """The ProfileData of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    return ProfileData.from_file(paths[0])


def _events(line: Any) -> list[tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def _leaves(ops: list[tuple[str, int, int]]) -> list[tuple[str, int, int]]:
    """Ops that hold no other op of the line, named by instruction."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [(n.split(" = ", 1)[0], s, e) for i, (n, s, e) in enumerate(ops)
            if i + 1 == len(ops) or not (ops[i + 1][1] < e
                                         and ops[i + 1][2] <= e)]


def _merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_line(pd: Any) -> tuple[list[tuple[str, int, int]], tuple[int, int]]:
    """Events of the host thread that holds the slice span, and the span."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = _events(line)
            spans = [(s, e) for n, s, e in events if n == SLICE]
            if spans:
                return events, spans[0]
    raise RuntimeError(f"no host span {SLICE!r} in the trace")


def _device_ops(pd: Any) -> list[list[tuple[str, int, int]]]:
    """Per device plane, its ops; a device plane without ops is an error."""
    chips = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        ops = [ev for line in plane.lines if line.name == OPS_LINE
               for ev in _events(line)]
        if ops:
            chips.append(ops)
    if not chips:
        raise RuntimeError(f"no device plane with an {OPS_LINE!r} line")
    return chips


def _doing(host: list[tuple[str, int, int]],
           times: list[int]) -> dict[int, str]:
    """What the host thread was doing at each time: the harness span
    around it > the innermost event. One sweep over events by start."""
    events = sorted((s, e, n) for n, s, e in host if n != SLICE)
    out: dict[int, str] = {}
    active: list[tuple[int, int, str]] = []
    i = 0
    for t in sorted(set(times)):
        while i < len(events) and events[i][0] <= t:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] > t]
        if not active:
            out[t] = "harness loop"
            continue
        inner = min(active, key=lambda ev: ev[1] - ev[0])[2]
        harness = [ev for ev in active if ev[2].startswith(HARNESS_PREFIX)]
        outer = (min(harness, key=lambda ev: ev[1] - ev[0])[2]
                 if harness else None)
        out[t] = inner if outer in (None, inner) else f"{outer} > {inner}"
    return out


def reduce(pd: Any) -> dict[str, Any]:
    """busy_s and window_s (busy averaged over chips), idle share, the ops
    that took most device time, and the longest idle gaps by host work."""
    host, (w0, w1) = _host_line(pd)
    chips = _device_ops(pd)
    busy_ns = 0
    op_ns: dict[str, int] = defaultdict(int)
    gaps: list[tuple[int, int]] = []
    for ops in chips:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        for n, s, e in _leaves(clipped):
            op_ns[n] += e - s
        merged = _merge([(s, e) for _, s, e in clipped])
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) // 2))
    doing = _doing(host, [mid for _, mid in gaps])
    gaps_named = [(ns, doing[mid]) for ns, mid in gaps]
    window_ns = w1 - w0
    busy = busy_ns / len(chips)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps_named, key=lambda g: -g[0])[:TOP]
    idle_by_cause: dict[str, int] = defaultdict(int)
    for ns, cause in gaps_named:
        idle_by_cause[cause] += ns
    return {
        "busy_s": busy / 1e9,
        "window_s": window_ns / 1e9,
        "idle_pct": 100.0 * (1.0 - busy / window_ns),
        "chips": len(chips),
        "device_ops": [[n, ns / 1e9] for n, ns in top_ops],
        "idle_gaps": [[cause, ns / 1e9] for ns, cause in top_gaps],
        "idle_by_cause": sorted(([c, ns / 1e9 / len(chips)]
                                 for c, ns in idle_by_cause.items()),
                                key=lambda kv: -kv[1])[:TOP],
    }
