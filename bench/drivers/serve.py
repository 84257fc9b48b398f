"""Traffic driver ``serve``: a closed loop of search jobs through one
``SearchServer``.

Traffic parameters: ``lanes``, ``segment_len`` and ``policy`` of the
server; ``outstanding``, the jobs kept in the system (a retired job is
replaced by a new one at once); ``budgets``, the generation budgets of one
block of jobs as {budget: count}, each block served in an order drawn
from the run seed; ``check_jobs``, how many retired jobs the comparison
replays (a sample drawn from the run seed, the longest job among them).
Every job searches the configuration's first dataset with its own seed.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from counts import pop_generation as work
from inputs import doping_genomes, rng_for, training_split
from drivers.search import differs, ga_config, host_state, search_of
from reference import ga


class Cell:
    spans = ("serve.submit", "serve.step")

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.api import MLPTopology, Problem, SearchServer

        ds = config["datasets"][0]
        self.dataset = ds
        self.traffic = traffic
        self.search = search_of(config, traffic, ds)
        self.x, self.y = training_split(ds["name"], seed, ds["rows"])
        self.rng = rng_for(seed, 2)
        self.doping = doping_genomes(ga.layout(self.search),
                                     config["doping_genomes"], self.rng)
        cfg = ga_config(self.search, config["generations"], 0)
        self.problem = Problem.from_data(MLPTopology(self.search.sizes),
                                         self.x, self.y, cfg,
                                         baseline_acc=ds["baseline_acc"])
        self.server = SearchServer.for_problems(
            [self.problem], n_lanes=traffic["lanes"],
            segment_len=traffic["segment_len"], policy=traffic["policy"])
        self.block = [int(b) for b, n in traffic["budgets"].items()
                      for _ in range(n)]
        self.pending = []
        self.jobs = {}            # job id → (budget, seed, submitted at)
        self.retired = []         # (JobResult, latency in s)
        self.check_rng = rng_for(seed, 3)

    def submit(self, budget=None):
        from repro.api import SearchJob

        if budget is None:
            if not self.pending:
                self.pending = [int(b) for b in
                                self.rng.permutation(self.block)]
            budget = self.pending.pop()
        seed = int(self.rng.integers(0, 2**31 - 1))
        with TraceAnnotation("serve.submit"):
            jid = self.server.submit(SearchJob(self.problem, budget,
                                               seed=seed,
                                               doping_seeds=self.doping))
        self.jobs[jid] = (budget, seed, time.perf_counter())

    def step(self):
        with TraceAnnotation("serve.step"):
            return self.server.step()

    def warm(self):
        """One admit → segment → retire pass through every lane."""
        for _ in range(self.traffic["lanes"]):
            self.submit(self.traffic["segment_len"])
        while self.server.has_work:
            self.step()
        self.jobs.clear()

    def window(self, seconds: float) -> dict:
        seg = self.traffic["segment_len"]
        lane_gens, admitted, ends = 0, set(), []
        t0 = time.perf_counter()
        for _ in range(self.traffic["outstanding"]):
            self.submit()
        while True:
            out = self.step()
            t = time.perf_counter()
            ends.append(t)
            active = self.server.active_jobs
            admitted.update(active.values())
            admitted.update(r.job_id for r in out)
            lane_gens += (len(out) + len(active)) * seg
            for r in out:
                self.retired.append((r, t - self.jobs[r.job_id][2]))
            if t - t0 >= seconds:
                break
            for _ in out:
                self.submit()
        lat = np.asarray([lt for _, lt in self.retired])
        s, rows = self.search, self.dataset["rows"]
        k = s.n_device_samples if s.variation_mode != "off" else 1
        chromos = (lane_gens + len(admitted)) * s.pop
        return {
            "window_s": t - t0, "unit_s": np.diff([t0] + ends),
            "attempted": len(self.retired),
            "failed": sum(not r.ok for r, _ in self.retired),
            "jobs_per_s": len(self.retired) / (t - t0),
            "job_p95_s": float(np.percentile(lat, 95)),
            "lane_generations": lane_gens,
            "mlp_ops": work.ops(chromos, rows, s.sizes, k),
        }

    def release(self):
        """Draw the jobs to compare, copy them to the host, drop the
        server."""
        n = len(self.retired)
        longest = max(range(n), key=lambda i: self.retired[i][0].generations)
        pick = self.check_rng.permutation(n)[:self.traffic["check_jobs"]]
        pick = [longest] + [int(i) for i in pick if i != longest]
        pick = pick[:self.traffic["check_jobs"]]
        self.outputs = []
        for i in pick:
            r = self.retired[i][0]
            budget, seed, _ = self.jobs[r.job_id]
            self.outputs.append((budget, seed, r.ok and host_state(r.state),
                                 r.front))
        self.retired = [(None, lt) for _, lt in self.retired]
        self.server = None

    def replay(self, search: ga.Search | None = None) -> list:
        """The compared jobs as the plain reference gives them (at
        ``search``, the cell's own by default)."""
        ref = ga.Reference(search or self.search, self.x, self.y,
                           self.dataset["baseline_acc"])
        out = []
        for budget, seed, _, _ in self.outputs:
            state, _, front = ref.run(seed, budget, self.doping)
            out.append((budget, seed, state, front))
        return out

    def check(self) -> list:
        """Each compared job against its standalone reference search."""
        states_off = fronts_off = 0
        for (_, _, state, front), (_, _, rs, rf) in zip(self.outputs,
                                                        self.replay()):
            s, f = differs(state, front, rs, rf) if state else (True, True)
            states_off += s
            fronts_off += f
        return [("states_off", states_off, 0), ("fronts_off", fronts_off, 0)]
