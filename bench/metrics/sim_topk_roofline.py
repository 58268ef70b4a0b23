"""Percent of its roofline that the ``sim_topk`` kernel reached in the
traced window: the least time its calls could take at the chip's peaks
over the device time they took.

A call with B queries against N index rows of width D does 2*B*N*D
operations and must move its inputs and outputs once: 4*(B*D + N*D)
bytes of float32 and 8*B bytes of results. Its least time is the larger
of operations over peak operations per second and bytes over peak bytes
per second; the note says which bounds the window's calls.
"""
from bench import trace

PROGRAM = "jit_sim_topk"


def cost(b: int, n: int, d: int) -> tuple[float, float]:
    """(operations, bytes) one call needs at the least."""
    return 2.0 * b * n * d, 4.0 * (b * d + n * d) + 8.0 * b


def read(run):
    if not run.topk_shapes or not run.events:
        return None
    took = trace.program_calls(run.events, PROGRAM,
                               trace.windows(run.events))
    if len(took) != len(run.topk_shapes):
        run.notes.append(f"sim_topk_roofline: {len(took)} {PROGRAM} "
                         f"programs in the trace for "
                         f"{len(run.topk_shapes)} calls; not read")
        return None
    peak = trace.peaks(run.device_kind)
    least, bound = 0.0, {}
    for b, n, d in run.topk_shapes:
        s, which = trace.least_seconds(*cost(b, n, d), peak)
        least += s
        bound[which] = bound.get(which, 0) + 1
    run.notes.append(f"sim_topk_roofline: {len(took)} calls, least "
                     f"{least:.6f} s ({bound}) against {sum(took):.6f} s "
                     f"on the device")
    return 100.0 * least / sum(took)
