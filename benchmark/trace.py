"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is held as ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``. ``load`` reads that form from a
``.json`` / ``.json.gz`` file (the small recorded trace kept for the tests)
or builds it from the profiler's ``.xplane.pb`` with
``jax.profiler.ProfileData``, which needs nothing but jax. The metrics never
look at the file, only at this form, so every PR reduces a trace in the same
way.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed operation, nested where an operation (a while loop, a
conditional, a call) runs others. Busy time is the union of those intervals;
an operation's own time is its duration less that of the operations nested
in it, so a loop and its body are not counted twice.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
NO_HOST_EVENT = "_no_host_event_"


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """The profiler names a device operation by its whole HLO instruction;
    keep the instruction's name and the type of its result:
    ``%fusion.16 = f32[73500000]{0:T(1024)} fusion(...)`` becomes
    ``fusion.16 f32[73500000]``."""
    if " = " not in name:
        return name
    left, right = name.split(" = ", 1)
    result = right.split("{", 1)[0].split(" ", 1)[0]
    return f"{left.lstrip('%')} {result}"[:120]


def load(path: str) -> dict:
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        host = plane.name.startswith("/host:CPU")
        if not (host or plane.name.startswith("/device:")):
            continue
        lines = []
        for line in plane.lines:
            if not host and line.name != OPS_LINE:
                continue
            events = [[short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)]
                      for e in line.events if e.duration_ns > 0]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def clip(trace: dict, t0_ns: float, t1_ns: float) -> dict:
    """The events that start inside [t0, t1)."""
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            ev = [e for e in line["events"] if t0_ns <= e[1] < t1_ns]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        planes.append({"name": plane["name"], "lines": lines})
    return {**trace, "planes": planes}


def device_ops(trace: dict) -> dict:
    """{device plane name: events of its XLA Ops line, sorted by start}."""
    out = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[plane["name"]] = sorted(line["events"],
                                            key=lambda e: (e[1], -e[2]))
    return out


def union(intervals) -> list:
    """Merged [start, end] intervals of (start, duration) pairs."""
    merged = []
    for start, dur in sorted(intervals):
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def busy_ns(events) -> float:
    return sum(e - s for s, e in union((ev[1], ev[2]) for ev in events))


def own_time_by_name(events) -> dict:
    """{operation name: seconds of its own}, nesting taken out. ``events``
    sorted by (start, -duration)."""
    own = defaultdict(float)
    stack = []           # [name, end, children_ns, dur]
    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, kids, dur = stack.pop()
            own[name] += max(dur - kids, 0.0)
    for name, start, dur in events:
        close(start)
        if stack:
            stack[-1][2] += dur
        stack.append([name, start + dur, 0.0, dur])
    close(float("inf"))
    return {k: v / 1e9 for k, v in own.items()}


def matching_ns(events, predicate) -> float:
    """Union of the intervals of the events whose name ``predicate`` takes."""
    return sum(e - s for s, e in union(
        (ev[1], ev[2]) for ev in events if predicate(ev[0])))


def fullest(trace: dict) -> tuple:
    """(plane name, events) of the device with most busy time."""
    best = None
    for name, events in device_ops(trace).items():
        b = busy_ns(events)
        if best is None or b > best[0]:
            best = (b, name, events)
    if best is None:
        return None, []
    return best[1], best[2]


def host_annotations(trace: dict) -> list:
    """Host events sorted by start: [name, start_ns, duration_ns]."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/host:CPU"):
            for line in plane["lines"]:
                out.extend(line["events"])
    return sorted(out, key=lambda e: e[1])


def idle_gaps(trace: dict, window: tuple, top: int = 10) -> list:
    """[[what the host was doing, seconds], ...]: the idle time of the
    fullest device inside ``window`` (ns), by the innermost host event that
    covers the middle of each gap."""
    _, events = fullest(trace)
    if not events:
        return []
    lo, hi = window
    busy = union((e[1], e[2]) for e in events)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    host = host_annotations(trace)
    by = defaultdict(float)
    for s, e in gaps:
        if e <= s:
            continue
        mid, name, width = (s + e) / 2, NO_HOST_EVENT, None
        for hn, hs, hd in host:
            if hs > mid:
                break
            if hs + hd >= mid and (width is None or hd < width):
                name, width = hn, hd
        by[name] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def breakdown(trace: dict, window: tuple, top: int = 10) -> dict:
    _, events = fullest(trace)
    own = own_time_by_name(events)
    ops = sorted(own.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": idle_gaps(trace, window, top)}


def busy_and_window(trace: dict, window: tuple) -> tuple:
    """(busy seconds averaged over the devices that ran anything, window
    seconds)."""
    per = [busy_ns(ev) for ev in device_ops(trace).values() if ev]
    if not per:
        return 0.0, (window[1] - window[0]) / 1e9
    return sum(per) / len(per) / 1e9, (window[1] - window[0]) / 1e9
