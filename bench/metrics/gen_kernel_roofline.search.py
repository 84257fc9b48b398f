"""Share of the generation megakernel's roofline: the least time one
generation's variation and fitness can take on the chip, the larger of
its operations over the int8 peak and its bytes over the HBM bandwidth
(``bench/counts/pop_generation.py``), over the kernel's device time per
generation (as ``gen_kernel_ms.search`` reads it)."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "gen_kernel_ms", Path(__file__).with_name("gen_kernel_ms.search.py"))
_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ms)


def read(ctx):
    ms = _ms.read(ctx)
    if ms is None:
        return None
    r, p = ctx["result"], ctx["peaks"]
    least = max(r["generation_ops"] / p["int8_ops_per_s"],
                r["generation_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
