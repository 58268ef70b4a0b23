"""From a profiler trace to the benchmark's device numbers.

A trace is reduced to a flat list of events ``(plane, line, name,
start_ns, duration_ns)``: every event of the device planes, and the
host's ``bench.*`` spans (``bench.instrument.spans``). On that list:

* busy time: the union of the device's op intervals inside the window
  (the ``bench.window`` spans), and the idle share, 1 - busy / window;
* program time by name: the device's program (XLA module) events summed
  by name, which is where a kernel's time is read;
* idle gaps: each stretch of the window with no device op, split over
  the innermost host span that covers it, summed by span name.

``peaks.json`` holds each chip's published peaks; a roofline share is the
least time a call could take at those peaks over the time it took.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict
                  ) -> tuple[float, str]:
    """Roofline: the larger of compute time and memory time at peak,
    and which of the two it is."""
    compute = flops / peak["flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def load_profile(log_dir: str) -> list[tuple]:
    """Flatten the ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir`` to the event list this module reduces."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    events = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith(DEVICE_PLANE)
            for line in plane.lines:
                for ev in line.events:
                    if device or ev.name.startswith(SPAN_PREFIX):
                        events.append((plane.name, line.name, ev.name,
                                       int(ev.start_ns),
                                       int(ev.duration_ns)))
    return events


def save(events: list[tuple], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: str) -> list[tuple]:
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def device_planes(events) -> list[str]:
    return sorted({e[0] for e in events if e[0].startswith(DEVICE_PLANE)})


def windows(events) -> list[tuple[int, int]]:
    """The ``bench.window`` spans, the measured window on the trace
    clock: one span, or one per timed operation where the run does
    untimed work between them."""
    spans = union((e[3], e[3] + e[4]) for e in events if e[2] == WINDOW_SPAN)
    if not spans:
        raise ValueError("trace holds no bench.window span")
    return spans


def _inside(t: int, wins) -> bool:
    return any(a <= t < b for a, b in wins)


def busy(events, plane: str, wins) -> list[tuple[int, int]]:
    """Union of one device's op intervals, clipped to the windows."""
    ops = union((e[3], e[3] + e[4]) for e in events
                if e[0] == plane and e[1] == OPS_LINE)
    return [iv for a, b in wins for iv in clip(ops, a, b)]


def busy_seconds(events) -> tuple[float, float]:
    """(device busy seconds averaged over the device planes, window
    seconds) of the traced window."""
    wins = windows(events)
    length = sum(b - a for a, b in wins) / 1e9
    planes = device_planes(events)
    if not planes:
        return 0.0, length
    total = sum(b - a for p in planes for a, b in busy(events, p, wins))
    return total / len(planes) / 1e9, length


def _programs(events, wins):
    for plane, line, name, start, dur in events:
        if (plane.startswith(DEVICE_PLANE) and line == MODULES_LINE
                and (wins is None or _inside(start, wins))):
            yield name, start, dur


def program_seconds(events, wins=None) -> dict[str, float]:
    """Device seconds by program (XLA module) name, summed over devices;
    only programs that start inside the windows when they are given."""
    out: dict[str, float] = {}
    for name, _, dur in _programs(events, wins):
        out[name] = out.get(name, 0.0) + dur / 1e9
    return out


def program_calls(events, prefix: str, wins=None) -> list[float]:
    """Device seconds of each run of the programs whose name starts with
    ``prefix``, in start order."""
    return [dur / 1e9 for _, dur in sorted(
        (start, dur) for name, start, dur in _programs(events, wins)
        if name.startswith(prefix))]


def idle_gaps(events) -> dict[str, float]:
    """Idle device seconds in the windows, by the innermost ``bench.*``
    host span over them ("other" where none but a window is)."""
    wins = windows(events)
    planes = device_planes(events)
    busy_iv = busy(events, planes[0], wins) if planes else []
    gaps = []
    for lo, hi in wins:
        t = lo
        for a, b in busy_iv:
            if b <= lo or a >= hi:
                continue
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
    lo, hi = wins[0][0], wins[-1][1]
    spans = sorted((e[3], e[3] + e[4], e[2]) for e in events
                   if e[2].startswith(SPAN_PREFIX) and e[2] != WINDOW_SPAN)
    # elementary pieces between every span and gap edge; each piece of a
    # gap goes to the covering span that started last (the innermost)
    edges = sorted({x for a, b in gaps for x in (a, b)}
                   | {x for a, b, _ in spans for x in (a, b)
                      if lo <= x <= hi})
    out: dict[str, float] = {}
    gi = si = 0
    open_spans: list[tuple[int, int, str]] = []
    for x0, x1 in zip(edges, edges[1:]):
        while gi < len(gaps) and gaps[gi][1] <= x0:
            gi += 1
        if gi == len(gaps):
            break
        if not gaps[gi][0] <= x0 < gaps[gi][1]:
            continue
        while si < len(spans) and spans[si][0] <= x0:
            open_spans.append(spans[si])
            si += 1
        open_spans = [sp for sp in open_spans if sp[1] > x0]
        name = max(open_spans)[2] if open_spans else "other"
        out[name] = out.get(name, 0.0) + (x1 - x0) / 1e9
    return out


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
