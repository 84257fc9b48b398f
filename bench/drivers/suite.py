"""Traffic driver ``suite``: the paper's whole table in one call, back to
back until the window has passed. A call is one ``run_suite`` over every
dataset of the configuration × ``seeds`` searches, its cells split over a
mesh of ``mesh`` devices, and then the front of every cell
(``SuiteResult.front_at``).

Traffic parameters (``bench/traffic/<mix>.json``): ``seeds`` (searches per
dataset) and ``mesh`` (devices along the mesh's one ``"data"`` axis), and,
as in ``search``, ``variation_mode`` and ``n_device_samples``. Every call
of a run is the same call: the search seeds and each dataset's doping
genomes are drawn from the run seed. The comparison replays one cell of
each dataset at its own, unpadded topology with the plain reference, the
cells chosen from the run seed so that they lie on every device of the
mesh, and holds every call of the window to those replays and to the
window's first call.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

from counts import pop_generation as work
from drivers.search import STATE_FIELDS, differs, ga_config, search_of
from inputs import doping_genomes, rng_for, training_split
from reference import ga


def replay_cell(search: ga.Search, x, y, baseline_acc: float, seed: int,
                generations: int, doping) -> tuple:
    """(final state, front) of one suite cell as the plain reference runs
    it: a single search at the dataset's own topology ``search.sizes``."""
    state, _, front = ga.Reference(search, x, y, baseline_acc).run(
        seed, generations, doping)
    return state, front


class Cell:
    spans = ("suite.run", "suite.front")

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro.api import MLPTopology, Problem

        self.datasets = config["datasets"]
        self.generations = config["generations"]
        self.searches = [search_of(config, traffic, ds)
                         for ds in self.datasets]
        # the suite's GA settings; a replay puts in each cell's topology
        self.search = self.searches[0]
        self.n_seeds = traffic["seeds"]
        self.data = [training_split(ds["name"], seed, ds["rows"])
                     for ds in self.datasets]
        rng = rng_for(seed, 1)
        self.seeds = rng.integers(0, 2**31 - 1, self.n_seeds)
        self.doping = [doping_genomes(ga.layout(s), config["doping_genomes"],
                                      rng) for s in self.searches]
        # dataset d's cell at seed index (3d + r) mod n: with 8 seeds, two
        # cells a device, the five replays lie on all four devices and in
        # both of a device's lanes, whatever r
        r = int(rng.integers(0, self.n_seeds))
        self.replayed = [d * self.n_seeds + (3 * d + r) % self.n_seeds
                         for d in range(len(self.datasets))]
        cfg = ga_config(self.search, self.generations, 0)
        self.problems = [
            Problem.from_data(MLPTopology(s.sizes), x, y, cfg,
                              baseline_acc=ds["baseline_acc"])
            for s, (x, y), ds in zip(self.searches, self.data,
                                     self.datasets)]
        self.mesh = jax.make_mesh((traffic["mesh"],), ("data",))
        self.outputs = []

    def one(self):
        from repro.api import run_suite

        with TraceAnnotation("suite.run"):
            res = run_suite(self.problems, self.seeds,
                            doping_seeds=self.doping,
                            names=[ds["name"] for ds in self.datasets],
                            mesh=self.mesh)
        with TraceAnnotation("suite.front"):
            fronts = [res.front_at(i) for i in range(res.n_cells)]
        return res, fronts

    def warm(self):
        self.one()

    def window(self, seconds: float) -> dict:
        outputs, evaluated, ends = [], 0, []
        terms = [0, 0]
        t0 = time.perf_counter()
        while True:
            res, fronts = self.one()
            outputs.append(({f: getattr(res.states, f) for f in STATE_FIELDS},
                            res.positions, fronts))
            # Σ unique_evals over the cells, in two reads
            evaluated += int(np.asarray(res.init_evals, np.int64).sum()
                             + np.asarray(res.aux[2], np.int64).sum())
            # the padded-work counter, where the program has it
            counter = getattr(res, "fitness_terms", None)
            if counter is not None:
                terms = [a + b for a, b in zip(terms, counter())]
            t = time.perf_counter()
            ends.append(t)
            if t - t0 >= seconds:
                break
        self.outputs = outputs
        s, calls = self.search, len(outputs)
        per_search = (self.generations + 1) * s.pop
        k = s.n_device_samples if s.variation_mode != "off" else 1
        nominal = calls * len(self.datasets) * self.n_seeds * per_search
        out = {
            "window_s": t - t0, "unit_s": np.diff([t0] + ends),
            "attempted": calls, "failed": 0,
            "chromo_evals_per_s": nominal / (t - t0),
            "nominal_rows": nominal, "evaluated_rows": evaluated,
            "mlp_ops": sum(
                work.ops(calls * self.n_seeds * per_search, ds["rows"],
                         srch.sizes, k)
                for ds, srch in zip(self.datasets, self.searches)),
        }
        if terms[1]:
            out["fitness_terms"] = tuple(terms)
        return out

    def release(self):
        """Copy the window's outputs to the host, each cell in its
        dataset's own gene layout, and drop the program."""
        self.outputs = [self._cells(states, positions, fronts)
                        for states, positions, fronts in self.outputs]
        self.problems = self.mesh = None

    def _cells(self, states, positions, fronts) -> dict:
        host = {f: np.asarray(a) for f, a in states.items()}
        out = {}
        for i, front in enumerate(fronts):
            st = {f: a[i] for f, a in host.items()}
            st["pop"] = st["pop"][:, positions[i // self.n_seeds]]
            out[i] = (st, front)
        return out

    def replay(self, search: ga.Search | None = None) -> list:
        """The replayed cells as the plain reference gives them, each at
        its dataset's own topology and otherwise at ``search``'s settings
        (the cell's own by default): one call's outputs."""
        out = {}
        for i in self.replayed:
            d, k = divmod(i, self.n_seeds)
            s = dataclasses.replace(search or self.search,
                                    sizes=self.searches[d].sizes)
            x, y = self.data[d]
            out[i] = replay_cell(s, x, y, self.datasets[d]["baseline_acc"],
                                 int(self.seeds[k]), self.generations,
                                 self.doping[d])
        return [out]

    def check(self) -> list:
        """Every replayed cell of every call against the plain reference,
        and every cell of every later call against the first call."""
        [ref] = self.replay()
        off = [differs(*call[i], *ref[i]) for call in self.outputs
               for i in ref]
        first = self.outputs[0]
        off += [differs(*call[i], *first[i]) for call in self.outputs[1:]
                for i in call]
        return [("states_off", sum(s for s, _ in off), 0),
                ("fronts_off", sum(f for _, f in off), 0)]
