"""Smoke test of the GA hardware-approximation search on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded paper suite on four chips

One chip, at the paper's widths (population 256, full training splits),
through the entry points a user calls:

  (b) trainer — pendigits (16-5-10, 7,696 rows) through ``GATrainer`` with
      the default backends, which on TPU are the Pallas fitness kernel and
      generation megakernel, checked field for field against the same run
      on the jnp backends;
  (c) Monte-Carlo — the same check on cardio (21-3-3, 1,488 rows) under
      worst-case device variation over K = 8 instances;
  (d) service — a 4-lane ``SearchServer`` answering six jobs over cardio and
      pendigits with budgets of 4-12 generations; one retired job is
      checked bit for bit against its standalone ``GATrainer.run``.

``--chips 4`` runs only ``run_suite`` over the five paper datasets × 4
seeds with its cells sharded over a 4-chip mesh, checked front for front
against the same suite unsharded on one chip.

Exits 2 unless JAX's first device is a TPU. Compile and steady times are
printed as information. The last line of standard output is a JSON object
naming the device, printed only when every check passed.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.api import (BackendPolicy, GAConfig, GATrainer,  # noqa: E402
                       MLPTopology, Problem, enable_compile_cache, run_suite)
from repro.data import DATASETS, load_dataset  # noqa: E402
from repro.serve import SearchJob, SearchServer  # noqa: E402

POP = 256
GENERATIONS = 8
# the EvalCache leaf is left out: the megakernel passes it through untouched
STATE_FIELDS = ("pop", "obj", "viol", "rank", "crowd", "counts", "key", "gen")
# the jnp paths every kernel is checked against
JNP = BackendPolicy(fitness="ref", variation="ref", generation="ref",
                    ranking="sweep")
# (dataset, generations, seed) of the service phase's job stream
JOBS = (("cardio", 4, 0), ("pendigits", 12, 1), ("cardio", 8, 2),
        ("pendigits", 4, 3), ("cardio", 12, 4), ("pendigits", 8, 5))


def assert_states_equal(a, b, what):
    for name in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{what}: GAState.{name} differs")


def assert_fronts_equal(a, b, what):
    for key in ("objectives", "genomes"):
        np.testing.assert_array_equal(a[key], b[key],
                                      err_msg=f"{what}: front {key} differ")


def trainer(ds, cfg) -> GATrainer:
    return GATrainer(MLPTopology(ds.topology), ds.x_train, ds.y_train, cfg)


def timed(label, fn, steps):
    """Run ``fn`` twice: the first call compiles, the second is steady."""
    t0 = time.perf_counter()
    fn()
    t1 = time.perf_counter()
    out = fn()
    t2 = time.perf_counter()
    print(f"  {label}: first call {t1 - t0:.3f} s (compile included), "
          f"steady {(t2 - t1) / steps * 1e3:.3f} ms per generation",
          flush=True)
    return out


def kernel_vs_jnp(what, ds, cfg, generations):
    """(b)/(c): one run on ``cfg``'s backends against the same run on the
    jnp backends, field for field."""
    print(f"[{what}] {ds.name} {ds.topology}, {ds.x_train.shape[0]} rows, "
          f"pop {cfg.pop_size}, {generations} generations", flush=True)
    states = {}
    for label, c in (("default", cfg), ("jnp", cfg.with_backends(JNP))):
        tr = trainer(ds, c)
        states[label], _ = timed(label, lambda tr=tr: tr.run(generations),
                                 generations)
    assert_states_equal(states["default"], states["jnp"], what)
    print(f"[{what}] default backends == jnp backends: ok", flush=True)


def service(datasets, cfg, jobs, n_lanes=4, segment_len=4):
    """(d): a job stream through ``SearchServer``; the longest job on the
    last dataset is checked against its standalone trainer run."""
    problems = {name: Problem.from_data(MLPTopology(ds.topology), ds.x_train,
                                        ds.y_train, cfg)
                for name, ds in datasets.items()}
    server = SearchServer.for_problems(list(problems.values()),
                                       n_lanes=n_lanes,
                                       segment_len=segment_len)
    for name, gens, seed in jobs:
        server.submit(SearchJob(problems[name], gens, seed=seed,
                                name=f"{name}/{seed}"))
    t0 = time.perf_counter()
    results = server.drain()
    lane_gens = sum(r.generations_run for r in results)
    print(f"[service] {len(results)} jobs, {server.segments_done} segments, "
          f"{lane_gens} lane-generations in {time.perf_counter() - t0:.3f} s "
          f"(compile included)", flush=True)
    assert len(results) == len(jobs), f"{len(results)} of {len(jobs)} retired"
    assert all(r.ok for r in results), [r.error for r in results if not r.ok]
    last = list(datasets)[-1]
    name, gens, seed = max((j for j in jobs if j[0] == last),
                           key=lambda j: j[1])
    got = next(r for r in results if r.name == f"{name}/{seed}")
    tr = trainer(datasets[name],
                 dataclasses.replace(cfg, seed=seed, generations=gens))
    state, _ = tr.run()
    assert_states_equal(got.state, state, f"job {got.name}")
    assert_fronts_equal(got.front, tr.front(state), f"job {got.name}")
    assert (got.unique_evals, got.cache_hits) == (tr.unique_evals,
                                                  tr.cache_hits)
    print(f"[service] job {got.name} ({gens} generations) == its standalone "
          "GATrainer.run: ok", flush=True)


def sharded_suite(datasets, cfg, seeds, n_chips):
    """--chips 4: the suite's cells sharded over a mesh of ``n_chips``
    against the same suite on device 0, front for front."""
    problems = [Problem.from_data(MLPTopology(ds.topology), ds.x_train,
                                  ds.y_train, cfg) for ds in datasets]
    names = [ds.name for ds in datasets]
    mesh = jax.make_mesh((n_chips,), ("data",))
    results = {}
    for label, kw in (("sharded", {"mesh": mesh}), ("one chip", {})):
        t0 = time.perf_counter()
        res = run_suite(problems, seeds, names=names, **kw)
        jax.block_until_ready(res.states.pop)
        print(f"  {label}: {res.n_cells} cells in "
              f"{time.perf_counter() - t0:.3f} s (compile included)",
              flush=True)
        results[label] = res
    a, b = results["sharded"], results["one chip"]
    assert a.n_cells == b.n_cells and a.n_cells % n_chips == 0
    for i in range(a.n_cells):
        what = f"cell {a.cell(i)}"
        assert_fronts_equal(a.front_at(i), b.front_at(i), what)
        assert_states_equal(a.state_at(i), b.state_at(i), what)
    print(f"[suite] {a.n_cells} cells over {n_chips} chips == one chip, "
          "front for front: ok", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded suite on a 4-chip host")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              "TPU; refusing to run", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
          f"compile cache: {cache}", flush=True)
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    cfg = GAConfig(pop_size=POP, generations=GENERATIONS)
    if args.chips == 4:
        sharded_suite([load_dataset(n) for n in DATASETS], cfg,
                      seeds=[0, 1, 2, 3], n_chips=4)
    else:
        cardio, pendigits = load_dataset("cardio"), load_dataset("pendigits")
        kernel_vs_jnp("trainer", pendigits, cfg, GENERATIONS)
        kernel_vs_jnp("monte-carlo", cardio,
                      dataclasses.replace(cfg, variation_mode="worst",
                                          n_device_samples=8), GENERATIONS)
        service({"cardio": cardio, "pendigits": pendigits}, cfg, JOBS)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
