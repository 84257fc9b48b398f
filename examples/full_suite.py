"""The paper's whole experiment table in ONE dispatch.

    PYTHONPATH=src python examples/full_suite.py [n_seeds]

Tables I/II and Figs. 4/5 are *per-dataset* GA runs over five UCI-analog
workloads with five different MLP topologies. `sweep.run_suite` embeds every
topology into one padded max-shape layout (per-gene validity masks, masked
output argmax, canonical-zero padding) and runs the full
(dataset × seed) grid as a single vmapped program — each cell bit-identical
to the sequential per-dataset `GATrainer.run` it replaces. See
examples/quickstart.py for the single-dataset pipeline and
examples/hyperparam_sweep.py for the (seed × hyperparameter) grid; this
demo adds the last axis, the dataset.
"""
import sys
import time

import numpy as np

from repro.api import (enable_compile_cache, GAConfig, Problem, MLPTopology, GenomeSpec,
                       HardwareCost, calibrated_seeds,
                       exact_bespoke_baseline, train_float_mlp,
                       best_within_loss, run_suite, suite_spec)
from repro.data import load_dataset, DATASETS


def main():
    n_seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 2
    cfg = GAConfig(pop_size=64, generations=40)

    problems, dopings, baselines = [], [], {}
    for name in DATASETS:
        ds = load_dataset(name)
        topo = MLPTopology(ds.topology)
        fm = train_float_mlp(topo, ds.x_train, ds.y_train, ds.x_test,
                             ds.y_test, steps=400)
        bb = exact_bespoke_baseline(topo, fm, ds.x_test, ds.y_test)
        baselines[name] = bb
        problems.append(Problem.from_data(
            topo, ds.x_train, ds.y_train, cfg, baseline_acc=bb.accuracy))
        dopings.append(calibrated_seeds(GenomeSpec(topo), fm, ds.x_train))
        print(f"{name:>14}: topology {topo.sizes}, baseline "
              f"acc={bb.accuracy:.3f}, {bb.fa_count} FAs")

    print(f"\npadded layout: {suite_spec(problems).topo.sizes} — "
          f"{len(DATASETS)} datasets × {n_seeds} seeds, one dispatch...")
    t0 = time.time()
    result = run_suite(problems, range(n_seeds), doping_seeds=dopings,
                       names=list(DATASETS))
    print(f"suite done in {time.time() - t0:.1f}s "
          f"({result.n_cells} cells)\n")

    for name in DATASETS:
        bb = baselines[name]
        fas = []
        for i in result.cells_of(name):
            front = result.front_at(i)
            idx = best_within_loss(front["objectives"], 1 - bb.accuracy, 0.05)
            if idx is not None:
                fas.append(front["objectives"][idx, 1])
        if not fas:
            print(f"{name:>14}: no design within 5% of baseline accuracy")
            continue
        cost = HardwareCost.from_fa(int(np.mean(fas)))
        red = bb.fa_count / max(np.mean(fas), 1)
        print(f"{name:>14}: FA = {np.mean(fas):.0f} ± {np.std(fas):.0f} "
              f"({len(fas)}/{n_seeds} seeds feasible, ≤5% loss) — "
              f"{cost.area_cm2:.3f} cm², {red:.0f}× smaller than bespoke")


if __name__ == "__main__":
    enable_compile_cache()
    main()
