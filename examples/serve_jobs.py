"""An always-on GA search service over a heterogeneous job stream.

    PYTHONPATH=src python examples/serve_jobs.py

The batching ladder (seeds → configs → datasets) makes *homogeneous*
grids one dispatch, but real search traffic is a stream: jobs with
different datasets, seeds and generation budgets arriving at different
times. A static padded dispatch would run every lane for the longest
budget and hold the queue until the whole batch returns. `SearchServer`
instead advances a fixed set of lanes in compiled fixed-size segments
(one program, reused forever) and, between segments, retires lanes whose
generation budget is exhausted — returning that job's Pareto front
immediately — and admits queued jobs into the freed slots by padding
them into the shared max-shape layout at runtime.

Every retired job is bit-identical to its standalone sequential
`GATrainer.run` — the demo checks one job against its trainer to prove
it. See `repro/serve/__init__.py` for the architecture notes and
`benchmarks/kernel_bench.bench_serve` for the throughput numbers.

Act two is the fault-tolerant runtime: the same stream under
`Supervisor` (auto-checkpointing every 2 segments, per-segment lane
health checks) with a scheduled `ChaosPlan` kill mid-stream — the
process "dies", `Supervisor.recover` restarts from the newest valid
checkpoint, the never-admitted job comes back via `dropped_pending`,
and every job still retires bit-identical. See the **Serve-path
architecture → Fault tolerance** section of ROADMAP.md.
"""
import dataclasses
import shutil
import tempfile

import numpy as np

from repro.api import GAConfig, GATrainer, MLPTopology, Problem, enable_compile_cache
from repro.data import load_dataset
from repro.serve import (ChaosKill, ChaosPlan, FaultPolicy, SearchServer,
                         Supervisor)

POP, SEGMENT = 32, 8


def main():
    cfg = GAConfig(pop_size=POP, generations=64)
    datasets = {n: load_dataset(n) for n in ("cardio", "redwine",
                                             "breast_cancer")}
    problems = {n: Problem.from_data(MLPTopology(ds.topology), ds.x_train,
                                     ds.y_train, cfg)
                for n, ds in datasets.items()}

    # 4 lanes, 8-generation segments, longest-job-first admission
    srv = SearchServer.for_problems(list(problems.values()), n_lanes=4,
                                    segment_len=SEGMENT, policy="longest")

    # a heterogeneous stream: budgets spanning 4x, three topologies
    stream = [("cardio", 32, 0), ("redwine", 16, 0), ("breast_cancer", 8, 0),
              ("cardio", 16, 1), ("redwine", 32, 1), ("breast_cancer", 24, 1)]
    for name, gens, seed in stream:
        srv.submit(problems[name], generations=gens, seed=seed,
                   name=f"{name}/s{seed}/g{gens}")
    print(f"submitted {len(stream)} jobs ({len(srv.pending_jobs)} queued) "
          f"into 4 lanes, segment = {SEGMENT} generations\n")

    done = []
    while srv.pending_jobs or srv.active_jobs:
        retired = srv.step()
        done.extend(retired)
        names = ", ".join(r.name for r in retired) or "—"
        print(f"segment {srv.segments_done:2d}: retired [{names}] "
              f"({len(srv.active_jobs)} running, "
              f"{len(srv.pending_jobs)} queued)")
        # staggered submission: traffic keeps arriving mid-flight and
        # backfills lanes freed by retired jobs — no recompilation
        if srv.segments_done == 2:
            jid = srv.submit(problems["cardio"], generations=8, seed=7,
                             name="cardio/s7/g8 (late)")
            print(f"            ... job {jid} submitted mid-flight")

    print("\nper-job Pareto fronts (min error vs min area):")
    for r in sorted(done, key=lambda r: r.name):
        objs = np.asarray(r.front["objectives"])
        best = objs[objs[:, 0].argmin()]
        print(f"  {r.name:>22}: {len(objs):2d} points, best acc-loss "
              f"{best[0]:.3f} @ {best[1]:.0f} FAs  "
              f"(admitted seg {r.admitted_segment}, retired seg "
              f"{r.retired_segment}, {r.unique_evals} unique evals)")

    # the service contract: any job == its standalone sequential trainer
    name, gens, seed = stream[0]
    ds = datasets[name]
    tr = GATrainer(MLPTopology(ds.topology), ds.x_train, ds.y_train,
                   dataclasses.replace(cfg, seed=seed, generations=gens))
    state, _ = tr.run()
    served = next(r for r in done if r.name == f"{name}/s{seed}/g{gens}")
    assert np.array_equal(served.front["objectives"],
                          tr.front(state)["objectives"])
    print(f"\n{name}/s{seed}/g{gens} front bit-identical to its standalone "
          f"GATrainer.run — the serve path changes scheduling, not numerics")

    supervised_crash_demo(problems, done)


def supervised_crash_demo(problems, bare_results):
    """Kill the service mid-stream, recover from the newest valid
    checkpoint, and finish the same jobs bit-identical to the
    uninterrupted run above."""
    print("\n--- supervised crash demo ---")
    stream = [("cardio", 32, 0), ("redwine", 16, 0), ("cardio", 16, 1)]
    ckpt_dir = tempfile.mkdtemp(prefix="serve_jobs_ckpt_")
    try:
        policy = FaultPolicy(checkpoint_every=2)   # + lane health checks
        chaos = ChaosPlan(kill_after_segment=2)    # "power cut" at seg 3
        sup = Supervisor.for_problems(
            [problems[n] for n in ("cardio", "redwine")], policy,
            directory=ckpt_dir, chaos=chaos, n_lanes=2,
            segment_len=SEGMENT, scheduler_policy="longest")
        for dsname, gens, seed in stream:
            sup.submit(problems[dsname], generations=gens, seed=seed,
                       name=f"{dsname}/s{seed}/g{gens}")
        results = {}    # results delivered before the crash stay delivered
        try:
            while sup.server.has_work:
                for r in sup.step():
                    results[r.name] = r
        except ChaosKill:
            print(f"process killed after segment "
                  f"{sup.server.segments_done} — "
                  f"{sup.stats['checkpoints']} checkpoint(s) committed, "
                  f"{len(results)} job(s) already delivered")

        spec, cfg0 = sup.server.spec, problems["cardio"].cfg
        rec = Supervisor.recover(ckpt_dir, spec, cfg0, policy)
        print(f"recovered from checkpoint step {rec.recovered_step}; "
              f"{len(rec.dropped_pending)} queued job(s) handed back")
        for meta in rec.dropped_pending:   # never reached a lane: resubmit
            rec.submit(problems[meta["name"].split("/")[0]],
                       generations=meta["generations"], seed=meta["seed"],
                       name=meta["name"])
        for r in rec.drain():
            results[r.name] = r

        bare = {r.name: r for r in bare_results}
        for dsname, gens, seed in stream:
            jname = f"{dsname}/s{seed}/g{gens}"
            r = results[jname]
            assert r.ok, r.error
            if jname in bare:
                assert np.array_equal(r.front["objectives"],
                                      bare[jname].front["objectives"])
        print(f"all {len(stream)} jobs survived the crash bit-identical — "
              f"checkpoint + recovery change availability, not numerics")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
