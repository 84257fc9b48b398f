"""Hyperparameter sweep: a Table-II-style point grid in ONE dispatch.

    PYTHONPATH=src python examples/hyperparam_sweep.py [dataset]

The paper's GA outcome depends on the operator rates and the accuracy-loss
constraint; related work explores the approximation design space by
sweeping exactly these knobs. This example runs the whole
(seed × mutation_rate × crossover_rate) grid with `sweep.run_grid` — the
swept knobs are traced `Problem` leaves, so every cell (a full scanned GA
run) batches into a single compiled program instead of one retrain per
cell — then reports each cell's best design within 5% accuracy loss
(test accuracy, FA count, printed area/power), the paper's Table II view.
"""
import sys

import jax.numpy as jnp

from repro.api import (enable_compile_cache, GAConfig, Problem, MLPTopology, GenomeSpec,
                       HardwareCost, accuracy, calibrated_seeds,
                       exact_bespoke_baseline, train_float_mlp,
                       best_within_loss, run_grid)
from repro.data import load_dataset

SEEDS = (0, 1)
MUTATION_RATES = (0.01, 0.02, 0.05)
CROSSOVER_RATES = (0.5, 0.7)


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "breast_cancer"
    ds = load_dataset(name)
    topo = MLPTopology(ds.topology)
    spec = GenomeSpec(topo)
    print(f"== {name}: sweeping {len(SEEDS)} seeds × "
          f"{len(MUTATION_RATES)} mutation × {len(CROSSOVER_RATES)} "
          f"crossover rates ==")

    fm = train_float_mlp(topo, ds.x_train, ds.y_train, ds.x_test, ds.y_test,
                         steps=800)
    bb = exact_bespoke_baseline(topo, fm, ds.x_test, ds.y_test)
    doping = calibrated_seeds(spec, fm, ds.x_train)
    print(f"exact bespoke baseline: acc={bb.accuracy:.3f} fa={bb.fa_count}")

    problem = Problem.from_data(
        topo, ds.x_train, ds.y_train,
        GAConfig(pop_size=48, generations=40), baseline_acc=bb.accuracy)
    result = run_grid(problem, SEEDS,
                            mutation_rates=MUTATION_RATES,
                            crossover_rates=CROSSOVER_RATES,
                            doping_seeds=doping)
    print(f"{result.n_cells} GA runs in one dispatch "
          f"(grid shape {result.shape})\n")

    print("seed  pc    pm     test_acc  FA     area_cm2  power_mW  "
          "unique_evals")
    for i in range(result.n_cells):
        cell = result.cell(i)
        front = result.front_at(i)
        idx = best_within_loss(front["objectives"], 1 - bb.accuracy, 0.05)
        tag = (f"{cell['seed']:<5d} {cell['crossover_rate']:.2f}  "
               f"{cell['mutation_rate_gene']:.3f}")
        if idx is None:
            print(f"{tag}  NO_FEASIBLE_POINT")
            continue
        g = front["genomes"][idx]
        test_acc = float(accuracy(spec, jnp.asarray(g),
                                  jnp.asarray(ds.x_test),
                                  jnp.asarray(ds.y_test)))
        fa = int(front["objectives"][idx, 1])
        cost = HardwareCost.from_fa(fa)
        print(f"{tag}  {test_acc:.3f}     {fa:<6d} {cost.area_cm2:<9.2f} "
              f"{cost.power_mw:<9.1f} {result.unique_evals(i)}")

    best = None
    for i in range(result.n_cells):
        front = result.front_at(i)
        idx = best_within_loss(front["objectives"], 1 - bb.accuracy, 0.05)
        if idx is not None:
            fa = float(front["objectives"][idx, 1])
            if best is None or fa < best[1]:
                best = (result.cell(i), fa)
    if best is not None:
        c, fa = best
        red = bb.fa_count / max(fa, 1e-9)
        print(f"\nbest cell seed={c['seed']} pc={c['crossover_rate']:.2f} "
              f"pm={c['mutation_rate_gene']:.3f}: {red:.1f}x area reduction "
              f"vs exact baseline (≤5% accuracy loss)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
