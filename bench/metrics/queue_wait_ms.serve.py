"""Mean time a job waits in the search server's queue for a lane, by
Little's law over the traced window, from the program's
``serve.enqueue`` and ``serve.admit`` spans (profiler trace)."""
import spans


def read(ctx):
    return spans.queue_wait_ms(ctx["trace"])
