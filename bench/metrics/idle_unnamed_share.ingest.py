"""Percent of the traced window's device-idle time that lies under none
of the program's spans: 100 * unnamed idle / idle.

Device busy time and the window come from the profiler's trace. The
program's spans come from the window's commits (``IngestReport.spans``:
op, start and seconds on the realtime clock the profiler stamps). The
reduced trace keeps its host times relative to the session's start, so
the spans are placed on it by the benchmark's own spans that open first
inside four of them (``bench.chunk`` in ``ingest.chunk``, and so on):
the offset is the median gap between their starts, and the reader is
silent when those gaps spread over more than ``SPREAD_NS``. Each idle
stretch then goes to the innermost program span over it, as
``trace.idle_gaps`` splits idle time by the benchmark's spans; the note
gives the idle seconds by span. Silent for a program that reports no
spans.
"""
from bench import trace

HOST = "/host:program"
ANCHORS = {"ingest.chunk": "bench.chunk", "ingest.extract": "bench.extract",
           "ingest.score": "bench.score", "ingest.observe": "bench.observe"}
SPREAD_NS = 1_000_000


def offset_ns(spans, events) -> int | None:
    """Trace time minus program time, from the anchor pairs in order."""
    gaps = []
    for op, name in ANCHORS.items():
        ours = sorted(t0 for o, t0, _ in spans if o == op)
        theirs = sorted(e[3] for e in events if e[2] == name)
        if len(ours) != len(theirs):
            return None
        gaps += [b - a for a, b in zip(ours, theirs)]
    if not gaps or max(gaps) - min(gaps) > SPREAD_NS:
        return None
    return sorted(gaps)[len(gaps) // 2]


def split(run) -> dict[str, float] | None:
    """Idle seconds of the window by innermost program span ("other"
    under none), or None where the spans cannot be placed."""
    spans = [s for r, _ in run.commits for s in getattr(r, "spans", ())]
    if not spans or not run.events:
        return None
    shift = offset_ns(spans, run.events)
    if shift is None:
        run.notes.append("idle_unnamed_share.ingest: the program's spans "
                         "could not be placed on the trace; not read")
        return None
    # the window and the device, with the program's spans in place of
    # the benchmark's, named so that idle_gaps splits by them
    events = [e for e in run.events if e[2] == trace.WINDOW_SPAN
              or e[0].startswith(trace.DEVICE_PLANE)]
    events += [(HOST, "spans", trace.SPAN_PREFIX + op, t0 + shift,
                round(s * 1e9)) for op, t0, s in spans]
    n = len(trace.SPAN_PREFIX)
    return {"repro." + k[n:] if k != "other" else k: v
            for k, v in trace.idle_gaps(events).items()}


def read(run):
    gaps = split(run)
    idle = sum(gaps.values()) if gaps else 0.0
    if idle <= 0:
        return None
    run.notes.append(f"idle_unnamed_share.ingest: {idle:.6f} s idle by "
                     f"innermost span {dict(trace.top(gaps, 20))}")
    return 100.0 * gaps.get("other", 0.0) / idle
