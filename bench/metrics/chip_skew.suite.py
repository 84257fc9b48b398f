"""How unevenly the mesh's chips are loaded: (max − min) ÷ max of the
chips' busy time (the union of each chip's operation intervals) over the
traced window, in % (profiler trace)."""
import intervals


def read(ctx):
    trace = ctx["trace"]
    if not trace or len(trace.devices) < 2:
        return None
    busy = [intervals.busy_ns(trace, d) for d in trace.devices]
    return 100.0 * (max(busy) - min(busy)) / max(busy) if max(busy) else None
