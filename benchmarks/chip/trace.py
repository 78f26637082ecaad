"""Reduction of a profiler trace to the device's busy time, its heaviest
operations and its idle gaps.

The profiler's ``.xplane.pb`` is first turned into plain data: planes, each
with lines, each with events ``[name, start_ns, duration_ns]``.  Device
planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per operation run on that chip.  Busy time is the union of those intervals;
the window is the first ``op`` span's start to the last one's end, the
spans the harness writes as ``TraceAnnotation``s on the host plane.
"""

from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
ANCHOR = "op"


def load_xplane(path: str) -> dict:
    """The trace at ``path`` as plain data."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    return {"planes": [
        {"name": pl.name,
         "lines": [{"name": ln.name,
                    "events": [[ev.name, ev.start_ns, ev.duration_ns]
                               for ev in ln.events]}
                   for ln in pl.lines]}
        for pl in pd.planes]}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``: the HLO
    instruction's name without its number, so repeats add up."""
    return re.sub(r"\.\d+$", "", event_name.split(" = ", 1)[0].lstrip("%"))


def self_times(events: list) -> collections.Counter:
    """Seconds-in-nanoseconds of each event's own time: operations nest
    (a ``while`` holds its body's operations), so a child's time is taken
    off its parent's."""
    own = collections.Counter()
    stack: list[tuple[float, str]] = []          # (end, name) of open events
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        key = op_name(name)
        own[key] += dur
        if stack:
            own[stack[-1][1]] -= dur
        stack.append((start + dur, key))
    return own


def device_events(trace: dict) -> dict[str, list]:
    """Per device plane, the events of its operations line."""
    out = {}
    for pl in trace["planes"]:
        if not DEVICE_PLANE.match(pl["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        for name in OP_LINES:
            if name in lines:
                out[pl["name"]] = lines[name]
                break
    return out


def host_spans(trace: dict, names: set[str]) -> list[tuple[str, float, float]]:
    return [(ev[0], ev[1], ev[1] + ev[2])
            for pl in trace["planes"] if pl["name"].startswith("/host:")
            for ln in pl["lines"] for ev in ln["events"] if ev[0] in names]


def reduce(trace: dict, span_names: set[str], chips: int,
           top: int = 10) -> dict | None:
    """Busy and window seconds (busy averaged over the first ``chips``
    devices, the cell's), the ``top`` operations by device time and the
    ``top`` longest idle gaps, each named by the innermost host span
    covering its middle.  ``None`` when the trace holds no ``op`` span or
    no operation ran on the cell's devices."""
    spans = host_spans(trace, span_names | {ANCHOR})
    anchors = [(a, b) for n, a, b in spans if n == ANCHOR]
    planes = device_events(trace)
    ids = sorted(planes, key=lambda n: int(n.rsplit(":", 1)[1]))[:chips]
    devices = {d: planes[d] for d in ids}
    if not anchors or not any(devices.values()):
        return None
    lo, hi = min(a for a, _ in anchors), max(b for _, b in anchors)
    busy_ns, op_ns = [], collections.Counter()
    gaps = []
    for evs in devices.values():
        merged = _clip(_union([(s, s + d) for _, s, d in evs]), lo, hi)
        busy_ns.append(sum(b - a for a, b in merged))
        for name, ns in self_times([e for e in evs
                                    if lo <= e[1] < hi]).items():
            op_ns[name] += ns / chips
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        cover = [(t1 - t0, n) for n, t0, t1 in spans if t0 <= mid < t1]
        named.append([min(cover)[1] if cover else "none", (b - a) * 1e-9])
    return {"busy_s": sum(busy_ns) / chips * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "device_ops": [[n, ns * 1e-9] for n, ns in op_ns.most_common(top)],
            "idle_gaps": named}


def idle_share(rec) -> float | None:
    """Percent of the traced window with no operation on the device."""
    if rec.trace is None or not rec.trace["window_s"]:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])
