"""Pod-scale GA: island-parallel NSGA-II with ring migration.

Every island runs the SAME functional engine step (`repro.core.engine
.generation`) that `GATrainer` scans — island i initializes exactly like a
`GATrainer` with seed + i, evolves its shard locally under `shard_map`, and
exchanges its best chromosomes over a `lax.ppermute` ring. On one device the
ring is degenerate: migration is skipped and the run is bit-for-bit a
single-trainer run (see tests/test_engine.py). The final front is peeled
from the *feasible* chromosomes only.

On real hardware the mesh spans pods; here it runs on however many devices
the process sees (1 on CPU, or set
XLA_FLAGS=--xla_force_host_platform_device_count=8 for a multi-island demo).

    PYTHONPATH=src python examples/islands_ga.py --dataset cardio
"""
import argparse

import jax

from repro.api import run_islands, IslandConfig, GAConfig, MLPTopology, enable_compile_cache
from repro.data import load_dataset


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="breast_cancer")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0,
                    help="island i uses PRNG seed seed+i")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",))
    print(f"{n_dev} island(s) on mesh {mesh.shape}"
          + (" — degenerate ring, no migration" if n_dev == 1 else ""))

    ds = load_dataset(args.dataset)
    cfg = IslandConfig(ga=GAConfig(), island_pop=32, migrate_every=5,
                       n_migrants=4, rounds=args.rounds)
    front, spec = run_islands(MLPTopology(ds.topology), ds.x_train,
                              ds.y_train, mesh, cfg, seed=args.seed)
    print(f"global Pareto front ({len(front['objectives'])} feasible points):")
    for err, fa in front["objectives"][:10]:
        print(f"  err={err:.3f}  FA={int(fa)}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
