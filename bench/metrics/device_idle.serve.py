"""Share of the window in which no operation ran on the chip, mean over
the cell's chips (profiler trace)."""
import xplane


def read(ctx):
    trace = ctx["trace"]
    return xplane.idle_share(trace) if trace and trace.devices else None
