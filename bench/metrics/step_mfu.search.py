"""The window's nominal MLP operations (``bench/counts``: two per
shift-add term, every chromosome on the full training split and on all K
device instances) over the window's time and the chips' int8 peak."""


def read(ctx):
    r = ctx["result"]
    peak = ctx["chips"] * ctx["peaks"]["int8_ops_per_s"]
    return 100.0 * r["mlp_ops"] / r["window_s"] / peak
