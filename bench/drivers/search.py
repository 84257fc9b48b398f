"""Traffic driver ``search``: back-to-back whole searches through one
``GATrainer``, ``run()`` then ``front()``, until the window has passed.

Traffic parameters (``bench/traffic/<mix>.json``): ``variation_mode`` and
``n_device_samples`` (device-variation Monte-Carlo fitness; "off" scores
the nominal device only). Every search of a run is the same search: the
configuration's dataset and GA settings, one search seed and one set of
doping genomes, all drawn from the run seed. The comparison replays it
with the plain reference and holds every search of the window to it.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from counts import pop_generation as work
from inputs import doping_genomes, rng_for, training_split
from reference import ga

STATE_FIELDS = ("pop", "obj", "viol", "rank", "crowd", "gen", "key")


def search_of(config: dict, traffic: dict, dataset: dict) -> ga.Search:
    return ga.Search(
        tuple(dataset["topology"]), pop=config["pop"],
        pc=config["crossover_rate"], pm=config["mutation_rate_gene"],
        max_acc_loss=config["max_acc_loss"],
        doping_frac=config["doping_frac"],
        variation_mode=traffic.get("variation_mode", "off"),
        n_device_samples=traffic.get("n_device_samples", 1))


def ga_config(search: ga.Search, generations: int, seed: int):
    from repro.api import GAConfig

    return GAConfig(
        pop_size=search.pop, generations=generations,
        crossover_rate=search.pc, mutation_rate_gene=search.pm,
        doping_frac=search.doping_frac, max_acc_loss=search.max_acc_loss,
        seed=seed, variation_mode=search.variation_mode,
        n_device_samples=search.n_device_samples)


def host_state(state) -> dict:
    return {f: np.asarray(getattr(state, f)) for f in STATE_FIELDS}


def differs(state: dict, front: dict, ref_state: dict, ref_front: dict):
    """(state differs, front differs) from the reference, bit for bit."""
    s = any(not np.array_equal(state[f], ref_state[f]) for f in STATE_FIELDS)
    f = any(not np.array_equal(np.asarray(front[k]), ref_front[k])
            for k in ("objectives", "genomes"))
    return s, f


class Cell:
    spans = ("search.run", "search.front")

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.api import GATrainer, MLPTopology

        ds = config["datasets"][0]
        self.dataset = ds
        self.search = search_of(config, traffic, ds)
        self.generations = config["generations"]
        self.x, self.y = training_split(ds["name"], seed, ds["rows"])
        rng = rng_for(seed, 1)
        self.search_seed = int(rng.integers(0, 2**31 - 1))
        self.doping = doping_genomes(ga.layout(self.search),
                                     config["doping_genomes"], rng)
        self.trainer = GATrainer(
            MLPTopology(self.search.sizes), self.x, self.y,
            ga_config(self.search, self.generations, self.search_seed),
            baseline_acc=ds["baseline_acc"], doping_seeds=self.doping)
        self.outputs = []

    def one(self):
        with TraceAnnotation("search.run"):
            state, _ = self.trainer.run()
        with TraceAnnotation("search.front"):
            front = self.trainer.front(state)
        return state, front

    def warm(self):
        self.one()

    def window(self, seconds: float) -> dict:
        outputs, evaluated, ends = [], 0, []
        t0 = time.perf_counter()
        while True:
            outputs.append(self.one())
            evaluated += self.trainer.unique_evals
            t = time.perf_counter()
            ends.append(t)
            if t - t0 >= seconds:
                break
        self.outputs = outputs
        s = self.search
        nominal = len(outputs) * (self.generations + 1) * s.pop
        k = s.n_device_samples if s.variation_mode != "off" else 1
        return {
            "window_s": t - t0, "unit_s": np.diff([t0] + ends),
            "attempted": len(outputs), "failed": 0,
            "chromo_evals_per_s": nominal / (t - t0),
            "nominal_rows": nominal, "evaluated_rows": evaluated,
            "generations": len(outputs) * self.generations,
            "mlp_ops": work.ops(nominal, self.dataset["rows"], s.sizes, k),
            "generation_ops": work.ops(s.pop, self.dataset["rows"], s.sizes,
                                       k),
            "generation_bytes": work.generation_bytes(
                s.pop, self.dataset["rows"], s.sizes, k),
        }

    def release(self):
        """Copy the window's outputs to the host and drop the program."""
        self.outputs = [(host_state(st), fr) for st, fr in self.outputs]
        self.trainer = None

    def replay(self, search: ga.Search | None = None) -> list:
        """The window's outputs as the plain reference gives them (at
        ``search``, the cell's own by default): one search."""
        ref = ga.Reference(search or self.search, self.x, self.y,
                           self.dataset["baseline_acc"])
        state, _, front = ref.run(self.search_seed, self.generations,
                                  self.doping)
        return [(state, front)]

    def check(self) -> list:
        """Every search of the window against the plain reference."""
        [(ref_state, ref_front)] = self.replay()
        off = [differs(st, fr, ref_state, ref_front)
               for st, fr in self.outputs]
        return [("states_off", sum(s for s, _ in off), 0),
                ("fronts_off", sum(f for _, f in off), 0)]
