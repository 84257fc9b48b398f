"""The search server's host spans on the device trace's clock: the
device-idle time between segment programs that falls inside a named
span, and the time a job waits for a lane.

The spans are the program's own ``jax.profiler.TraceAnnotation``\\ s in
``repro.serve.server``: ``serve.enqueue`` (``submit``), ``serve.admit``
(one per admission), ``serve.dispatch``, ``serve.sync`` (the blocking
read of a segment's accounting) and ``serve.retire`` (one per
retirement). A trace without them reads None, never 0.
"""
import xplane

# segment programs, found as ``segment_gap_ms.serve`` finds them
SEGMENT = r"_run_segment"


def intersect(a, b):
    """Overlap of two sorted lists of disjoint [(start, end)]."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def segment_gap_in_ms(trace, span: str):
    """Device-idle time that lies inside ``span`` host spans and between
    two consecutive segment programs, summed and divided by the number of
    such intervals (the base of ``segment_gap_ms.serve``), mean over the
    chips, in ms."""
    if not trace:
        return None
    cover = xplane.union([h for h in trace.host if h[0] == span])
    if not cover:
        return None
    means = []
    for dev in trace.devices:
        segs = xplane.module_spans(trace, SEGMENT, dev)
        between = [(a[1], b[0]) for a, b in zip(segs, segs[1:])]
        if between:
            idle = intersect(intersect(xplane.gaps(trace, dev), between),
                             cover)
            means.append(sum(e - s for s, e in idle) / len(between))
    return sum(means) / len(means) * 1e-6 if means else None


def queue_wait_ms(trace):
    """Mean time a job waits for a lane, by Little's law: the integral of
    the queue length Q(t) over the window divided by the admissions in
    it, in ms. Q(t) is the ``serve.enqueue`` spans ended by t less the
    ``serve.admit`` spans started by t; the window opens on an empty
    queue."""
    if not trace:
        return None
    lo, hi = trace.window
    steps = sorted([(e, 1) for n, _, e in trace.host
                    if n == "serve.enqueue" and lo <= e <= hi]
                   + [(s, -1) for n, s, _ in trace.host
                      if n == "serve.admit" and lo <= s <= hi])
    admitted = sum(d < 0 for _, d in steps)
    if not admitted:
        return None
    area, queued, t = 0, 0, lo
    for at, d in steps:
        area += queued * (at - t)
        queued, t = queued + d, at
    area += queued * (hi - t)
    return area / admitted * 1e-6
