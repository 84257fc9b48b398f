"""Mean device-idle time between consecutive segment programs of the
search server (``repro.serve.server._run_segment``): the idle part of
each interval from one segment's end to the next one's start, mean over
the intervals and the chips (profiler trace)."""
import xplane

SEGMENT = r"_run_segment"


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    means = []
    for dev in trace.devices:
        spans = xplane.module_spans(trace, SEGMENT, dev)
        idle = [xplane.idle_ns(trace, dev, a[1], b[0])
                for a, b in zip(spans, spans[1:])]
        if idle:
            means.append(sum(idle) / len(idle))
    return sum(means) / len(means) * 1e-6 if means else None
