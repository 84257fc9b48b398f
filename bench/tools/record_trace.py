"""Record a small profiler trace of a search cell on the chip.

    python bench/tools/record_trace.py <out_dir> [--workload W] [--seconds S]
        [--generations G]

Runs the cell's driver, cut to ``G`` generations per search, traced for
``S`` seconds under the ``bench.window`` span, copies the ``.xplane.pb``
to ``<out_dir>/trace.xplane.pb`` and prints its planes, lines and the
reducer's reading. ``bench/tests/data/trace.xplane.pb.gz`` is one such
trace (the defaults: one 2-generation search of ``cardio.robust_k8`` with
``--generations 2 --seconds 0.01``), gzipped, for the reducer's tests.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir")
    ap.add_argument("--workload", default="cardio.robust_k8")
    ap.add_argument("--seconds", type=float, default=0.01)
    ap.add_argument("--generations", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    _, config, traffic = run.resolve(run.manifest(), args.workload)
    run.use_compile_cache()
    import jax
    import xplane
    from jax.profiler import ProfileData

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    config = dict(config, generations=args.generations)
    cell = run.driver(traffic).Cell(config, traffic, args.seed)
    cell.warm()
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    t0 = time.perf_counter()
    run.traced_window(cell, args.seconds, tmp)
    print(f"traced window + stop: {time.perf_counter() - t0:.3f} s")
    out = Path(args.out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(xplane.find_xplane(tmp), out / "trace.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    pd = ProfileData.from_file(str(out / "trace.xplane.pb"))
    for plane in pd.planes:
        lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
        print("plane", plane.name, lines[:12])
    tr = xplane.load(str(out / "trace.xplane.pb"))
    print("window_s", tr.window_s, "busy_s", xplane.busy_s(tr),
          "complete", xplane.complete(tr))
    print("breakdown", xplane.breakdown(tr, cell.spans))
    return 0


if __name__ == "__main__":
    sys.exit(main())
