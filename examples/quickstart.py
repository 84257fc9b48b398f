"""Quickstart: the paper's full pipeline on one dataset in ~a minute.

    PYTHONPATH=src python examples/quickstart.py [dataset]

float MLP → exact bespoke baseline → NSGA-II hardware-aware training →
area/accuracy Pareto front → Verilog for the chosen design, then the same
search repeated over 3 seeds in ONE `run_batch` dispatch (the paper
reports statistics over repeated GA runs — this is how to get them without
N sequential retrains), and finally the search rerun under device-variation
Monte-Carlo fitness (`GAConfig.variation_mode`) to compare robust vs
nominal fronts. To sweep GA *hyperparameters* (mutation/crossover rates,
the accuracy-loss bound) the same one-dispatch way, see `run_grid` in
examples/hyperparam_sweep.py — and to run ALL FIVE paper
datasets/topologies as one padded dispatch (the whole experiment table),
see `run_suite` in examples/full_suite.py. To serve a *stream* of such
searches as an always-on service — and to do it fault-tolerantly
(`Supervisor` + `FaultPolicy`: auto-checkpointing, crash recovery, lane
quarantine) — see examples/serve_jobs.py.

Everything imports through ``repro.api`` — the package's stable public
surface; scripts should not reach into ``repro.core.*`` internals.
"""
import dataclasses
import sys

import numpy as np
import jax.numpy as jnp

# repro.api is the package's stability boundary — examples import it and
# nothing deeper (repro.core/* internals may move under it)
from repro.api import (enable_compile_cache, GAConfig, GATrainer, MLPTopology, GenomeSpec,
                       HardwareCost, accuracy, calibrated_seeds,
                       exact_bespoke_baseline, train_float_mlp,
                       best_within_loss, emit_verilog, run_batch,
                       state_at, front_of)
from repro.data import load_dataset


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "breast_cancer"
    ds = load_dataset(name)
    topo = MLPTopology(ds.topology)
    spec = GenomeSpec(topo)
    print(f"== {name}: topology {topo.sizes}, {topo.n_params} params ==")

    fm = train_float_mlp(topo, ds.x_train, ds.y_train, ds.x_test, ds.y_test,
                         steps=800)
    bb = exact_bespoke_baseline(topo, fm, ds.x_test, ds.y_test)
    base = HardwareCost.from_fa(bb.fa_count)
    print(f"exact bespoke baseline: acc={bb.accuracy:.3f} "
          f"area={base.area_cm2:.2f}cm² power={base.power_mw:.1f}mW")

    seeds = calibrated_seeds(spec, fm, ds.x_train)
    # dedup defaults to the cross-generation EvalCache: re-discovered
    # chromosomes skip evaluation across the whole run (bit-identical
    # results either way). Knobs: dedup=True|"cache"|"legacy"|False,
    # cache_slots (table size, default 4096, rounded to a power of two),
    # cache_probes (probe depth). Backend selection is the single
    # backends=BackendPolicy(fitness=..., variation=..., generation=...,
    # ranking=...) knob — "auto" everywhere picks the Pallas kernels on
    # TPU and the tiled/fused jnp paths elsewhere; ranking="matrix"
    # selects the O(P²) dominance-matrix oracle (bit-identical to the
    # O(P log P) sweep). The old per-path *_backend kwargs still work
    # but emit a DeprecationWarning.
    trainer = GATrainer(topo, ds.x_train, ds.y_train,
                        GAConfig(pop_size=64, generations=60),
                        baseline_acc=bb.accuracy, doping_seeds=seeds)
    state, hist = trainer.run(verbose=True)
    print(f"unique rows evaluated: {trainer.unique_evals}, "
          f"cross-generation cache hits: {trainer.cache_hits}")
    front = trainer.front(state)
    print(f"Pareto front ({len(front['objectives'])} points):")
    for err, fa in front["objectives"][:8]:
        c = HardwareCost.from_fa(int(fa))
        print(f"  err={err:.3f}  FA={int(fa):4d}  area={c.area_cm2:.3f}cm²  "
              f"power={c.power_mw:.2f}mW")

    idx = best_within_loss(front["objectives"], 1 - bb.accuracy, 0.05)
    if idx is None:
        print("no design within 5% of baseline accuracy — rerun with more "
              "generations")
        return
    g = front["genomes"][idx]
    test_acc = float(accuracy(spec, jnp.asarray(g), jnp.asarray(ds.x_test),
                              jnp.asarray(ds.y_test)))
    fa = int(front["objectives"][idx, 1])
    ours = HardwareCost.from_fa(fa)
    print(f"\nselected (≤5% loss): test_acc={test_acc:.3f} "
          f"area={ours.area_cm2:.3f}cm² ({base.area_cm2 / ours.area_cm2:.0f}× "
          f"smaller) power={ours.power_mw:.2f}mW "
          f"({base.power_mw / ours.power_mw:.0f}× lower)")

    path = f"{name}_evolved.v"
    with open(path, "w") as f:
        f.write(emit_verilog(spec, g, name=f"{name}_mlp"))
    print(f"Verilog written to {path}")

    # -- repeated-run statistics: 3 seeds, one vmapped dispatch -------------
    n_seeds = 3
    states, _, _ = run_batch(trainer.problem, np.arange(n_seeds),
                             doping_seeds=seeds)
    best_fas = []
    for s in range(n_seeds):
        front_s = front_of(state_at(states, s))
        i = best_within_loss(front_s["objectives"], 1 - bb.accuracy, 0.05)
        if i is not None:
            best_fas.append(front_s["objectives"][i, 1])
    if best_fas:
        print(f"\n{len(best_fas)}/{n_seeds} seeds feasible (≤5% loss): "
              f"FA = {np.mean(best_fas):.0f} ± {np.std(best_fas):.0f} "
              f"(one run_batch dispatch)")

    # -- device-variation robustness: rerun the search with the Monte-Carlo
    # fitness (K perturbed device instances per chromosome; the front
    # grows a third robust-error column) and compare robust vs nominal ----
    mc_cfg = dataclasses.replace(trainer.cfg, variation_mode="worst",
                                 n_device_samples=8, variation_scale=0.2)
    mc = GATrainer(topo, ds.x_train, ds.y_train, mc_cfg,
                   baseline_acc=bb.accuracy, doping_seeds=seeds)
    mc_state, _ = mc.run()
    mc_front = mc.front(mc_state)
    print(f"\nrobust front under {mc_cfg.n_device_samples}-instance "
          f"device variation (scale={mc_cfg.variation_scale}, "
          f"mode={mc_cfg.variation_mode!r}) — nominal vs worst-instance:")
    for nom_err, fa, rob_err in mc_front["objectives"][:8]:
        print(f"  nominal err={nom_err:.3f}  worst-device err={rob_err:.3f}"
              f"  FA={int(fa):4d}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
