"""Device-idle time between consecutive segment programs that falls
inside the program's ``serve.retire`` spans (``SearchServer._retire``:
lane gathers, the front, the null-problem scatter), per interval, mean
over the chips: retirement's part of ``segment_gap_ms.serve`` (profiler
trace)."""
import spans


def read(ctx):
    return spans.segment_gap_in_ms(ctx["trace"], "serve.retire")
