"""Device-idle time between consecutive segment programs that falls
inside the program's ``serve.sync`` spans (``SearchServer.step``'s
blocking read of the segment's accounting), per interval, mean over the
chips: that read's part of ``segment_gap_ms.serve`` (profiler trace)."""
import spans


def read(ctx):
    return spans.segment_gap_in_ms(ctx["trace"], "serve.sync")
