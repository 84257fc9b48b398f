"""Device time of the generation megakernel per generation, per chip.

The kernel is found by the name the trace gives its operation, that of
the jitted function around its ``pallas_call``:
``repro.kernels.pop_generation.kernel.pop_generation_kernel``."""
import xplane

KERNEL = r"^pop_generation_kernel\b"


def read(ctx):
    trace, r = ctx["trace"], ctx["result"]
    if not trace or not trace.devices or not r["generations"]:
        return None
    ns = xplane.op_ns(trace, KERNEL) / len(trace.devices)
    return ns * 1e-6 / r["generations"] if ns else None
