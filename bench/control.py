"""The comparison's two readings for one cell, on several seeds, in one
process on the chip.

    python bench/control.py --workload <name> --seeds 1 2 3 [--seconds S]

For each seed the cell is built and warmed as in a benchmark run, and a
short window of ``S`` seconds runs at the cell's own load; with ``S`` 0
(enough for the search cells) one whole search stands for the window.
Then the compared numbers are read twice: for the program's own outputs
(the lower reading), and for the control, the plain reference with a
bfloat16 forward pass put in the program's place (the upper reading).
Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def readings(cell, seconds: float) -> dict:
    cell.warm()
    if seconds > 0:
        cell.window(seconds)
    else:
        cell.outputs = [cell.one()]
    cell.release()
    program = dict((n, v) for n, v, _ in cell.check())
    cell.outputs = cell.replay(dataclasses.replace(cell.search,
                                                   precision="bfloat16"))
    control = dict((n, v) for n, v, _ in cell.check())
    return {"program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    spec, config, traffic = run.resolve(run.manifest(), args.workload)
    run.use_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    drv = run.driver(traffic)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = readings(drv.Cell(config, traffic, seed), args.seconds)
        out.update(workload=args.workload, seed=seed,
                   seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
