"""Device-idle time between consecutive bucket programs of ``run_suite``
(``repro.core.sweep._run_suite_cells``, one per sample-size bucket) that
falls inside the program's ``suite.bucket`` spans (a bucket's lane
composition and its dispatch), per interval, mean over the chips
(profiler trace). A bucket program traced and lowered again inside the
window shows here as host time the chips wait through."""
import intervals
import spans
import xplane

BUCKET = r"_run_suite_cells"


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    cover = xplane.union([h for h in trace.host if h[0] == "suite.bucket"])
    if not cover:
        return None
    means = []
    for dev in trace.devices:
        progs = xplane.module_spans(trace, BUCKET, dev)
        between = [(a[1], b[0]) for a, b in zip(progs, progs[1:])]
        if between:
            idle = intervals.idle_within(trace, dev,
                                         spans.intersect(between, cover))
            means.append(idle / len(between))
    return sum(means) / len(means) * 1e-6 if means else None
