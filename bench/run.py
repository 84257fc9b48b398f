"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``. Its configuration is
``bench/configs/<config>.json``, its traffic mix ``bench/traffic/<mix>.json``,
whose ``driver`` names the general driver ``bench/drivers/<driver>.py``
that runs it, and each per-layer metric is read by
``bench/metrics/<metric>.py``. Set-up builds the inputs from the seed and
warms every program the window runs; the window then runs for
``--seconds``; once it has closed, the device's memory peak is read, the
program's state is freed and the window's outputs are compared with the
plain reference (``bench/reference``).

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and from the program's counters. The last line of standard output
is the result as one JSON object; each compared number and its limit are
the last lines of standard error. Exits 2, printing no result, unless JAX's
first device is a TPU and there are as many chips as the cell asks for.
"""
from __future__ import annotations

import argparse
import collections
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
sys.path.insert(0, str(BENCH))
import xplane  # noqa: E402
# a traced run traces this much of its window at most: the profiler keeps
# a bounded number of a chip's operations, and a search records hundreds
# of them per generation
TRACE_SECONDS = 3.0


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def resolve(bench: dict, workload: str):
    """(cell, configuration, traffic mix) of ``workload``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return cell, config, traffic


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics reported in a cell."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def driver(traffic: dict):
    return load_module(BENCH / "drivers" / f"{traffic['driver']}.py")


def use_compile_cache():
    """JAX's persistent compilation cache in the checkout, for every
    program of the run, small ones too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Compiles:
    """Persistent-cache hits, misses (each one a compilation) and backend
    compile seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = collections.Counter()
        self.secs = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.count[name.rsplit("/", 1)[-1]] += 1

    def _duration(self, name, secs, **_):
        self.secs[name.rsplit("/", 1)[-1]] += secs

    def snapshot(self) -> dict:
        return {"compiled": self.count["cache_misses"],
                "cache_hits": self.count["cache_hits"],
                "compile_s": round(self.secs["backend_compile_duration"], 3)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def traced_window(cell, seconds: float, trace_dir: str):
    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(trace_dir)
    try:
        with TraceAnnotation("bench.window"):
            result = cell.window(seconds)
    finally:
        jax.profiler.stop_trace()
    return result


def per_layer(bench, workload, ctx) -> dict:
    out = {}
    for m in cell_metrics(bench, workload, "per_layer"):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(bench: dict, workload: str, cell, devices, seconds: float,
            trace: bool, t_start: float) -> tuple:
    """Warm the cell, run its window, compare; → (result line, checks).
    How set-up went and what compiled when goes to standard error."""
    compiles = Compiles()
    t_warm = time.perf_counter()
    cell.warm()
    setup_s = time.perf_counter() - t_start
    warmed = compiles.snapshot()

    reduced = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            result = traced_window(cell, min(seconds, TRACE_SECONDS),
                                   trace_dir)
            reduced = xplane.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if not xplane.complete(reduced):
            print("bench: the profiler dropped operations; device metrics "
                  "of this run are short", file=sys.stderr)
    else:
        result = cell.window(seconds)
    window = {k: v - warmed[k] for k, v in compiles.snapshot().items()}
    units = sorted(result["unit_s"])
    print(f"bench: set-up {setup_s:.3f} s, of it warm-up "
          f"{setup_s - (t_warm - t_start):.3f} s; set-up {warmed}; "
          f"window {window}; {len(units)} units of {units[0]:.4f} / "
          f"{units[len(units) // 2]:.4f} / {units[-1]:.4f} s (min / "
          f"median / max)", file=sys.stderr)
    peak = memory_peak(devices)
    cell.release()
    checks = cell.check()

    kind = devices[0].device_kind
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": result["attempted"], "failed": result["failed"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        from peaks import peaks

        ctx = {"trace": reduced, "result": result, "chips": len(devices),
               "peaks": peaks(kind)}
        out["metrics"] = per_layer(bench, workload, ctx)
        device.update(busy_s=xplane.busy_s(reduced),
                      window_s=reduced.window_s)
        out["breakdown"] = xplane.breakdown(reduced, cell.spans)
    else:
        values = dict(result, setup_s=setup_s)
        out["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, workload, "end_to_end")}
    out["device"] = device
    out["compared"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in checks}
    return out, checks


def run(args) -> int:
    t_start = time.perf_counter()
    bench = manifest()
    spec, config, traffic = resolve(bench, args.workload)
    use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        print(f"bench: {args.workload} needs {spec['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cell = driver(traffic).Cell(config, traffic, args.seed)
    out, checks = execute(bench, args.workload, cell,
                          devices[:spec["chips"]], args.seconds,
                          bool(args.trace), t_start)
    for name, value, limit in checks:
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
