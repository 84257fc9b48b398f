"""Device-idle time between consecutive segment programs that falls
inside the program's ``serve.admit`` spans (``SearchServer._admit``: pad,
init, lane scatters), per interval, mean over the chips: admission's
part of ``segment_gap_ms.serve`` (profiler trace)."""
import spans


def read(ctx):
    return spans.segment_gap_in_ms(ctx["trace"], "serve.admit")
