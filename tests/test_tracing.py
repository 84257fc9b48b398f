"""The program's profiler names: the host spans of ``SearchServer`` and
``GATrainer`` land in a profiler trace, in step order and with their job
ids, and the XLA phases of the generation step carry their named scopes
in the lowered program's ``op_name`` metadata."""
import glob
import re

import pytest
import jax
from jax.profiler import ProfileData

from repro.core import GAConfig, GATrainer
from repro.core import engine
from repro.core.genome import MLPTopology
from repro.kernels import BackendPolicy
from repro.serve import SearchJob, SearchServer

SERVE_SPANS = ("serve.enqueue", "serve.admit", "serve.dispatch",
               "serve.sync", "serve.retire")
GA_SPANS = ("ga.init", "ga.scan", "ga.front")
# the device scopes each generation path puts around its XLA phases
REF_SCOPES = ("ga.variation", "ga.cache", "ga.fitness", "ga.objectives",
              "ga.rank", "ga.survive")
KERNEL_SCOPES = ("ga.parents", "ga.objectives", "ga.rank", "ga.survive")
# the megakernel path scopes the fitness operands it computes in XLA:
# the device-variation deltas
MC = {"variation_mode": "worst", "n_device_samples": 2}


def host_spans(trace_dir, names):
    """[(name, start, end, args)] of the named host spans, by start."""
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
                       for e in line.events if e.name in names)
    return sorted(out, key=lambda s: s[1])


def _problem(ds, cfg):
    return engine.Problem.from_data(MLPTopology(ds.topology), ds.x_train,
                                    ds.y_train, cfg)


def test_server_and_trainer_spans(bc_dataset, tmp_path):
    ds = bc_dataset
    cfg = GAConfig(pop_size=16, generations=4,
                   backends=BackendPolicy(fitness="ref", generation="ref"))
    problem = _problem(ds, cfg)
    server = SearchServer.for_problems([problem], n_lanes=2, segment_len=2)
    trainer = GATrainer(MLPTopology(ds.topology), ds.x_train, ds.y_train,
                        cfg)
    budgets = (2, 6, 2)
    # compile every program outside the trace
    server.submit(SearchJob(problem, 2, seed=9))
    server.drain()
    trainer.front(trainer.run()[0])

    with jax.profiler.trace(str(tmp_path)):
        ids = [server.submit(SearchJob(problem, b, seed=i))
               for i, b in enumerate(budgets)]
        steps = []
        while server.has_work:
            steps.append([r.job_id for r in server.step()])
        trainer.front(trainer.run()[0])

    spans = host_spans(tmp_path, SERVE_SPANS + GA_SPANS)
    names = [s[0] for s in spans]
    assert set(names) == set(SERVE_SPANS + GA_SPANS)

    def args(name, key="job"):
        return [s[3][key] for s in spans if s[0] == name]

    retired = [j for step in steps for j in step]
    assert args("serve.enqueue") == ids
    # every job admitted once and retired once, in the order returned
    assert sorted(args("serve.admit")) == ids
    assert args("serve.retire") == retired and sorted(retired) == ids
    assert names.count("serve.dispatch") == len(steps)
    assert all(0 <= lane < 2 for lane in args("serve.admit", "lane"))
    assert args("serve.dispatch", "lanes") == [2, 2, 1]

    # within each step: admissions, then the dispatch, the blocking read
    # of its accounting, then the retirements
    order = "".join({"serve.admit": "a", "serve.dispatch": "d",
                     "serve.sync": "s", "serve.retire": "r"}[n]
                    for n in names if n in SERVE_SPANS[1:])
    assert re.fullmatch(r"(a*dsr*)+", order), order
    per_step = re.findall(r"a*dsr*", order)
    assert [step.count("r") for step in per_step] == [len(s) for s in steps]

    ga = [n for n in names if n in GA_SPANS]
    assert ga == ["ga.init", "ga.scan", "ga.front"]


@pytest.mark.parametrize("backend, mc, scopes", [
    ("ref", {}, REF_SCOPES),
    ("interpret", {}, KERNEL_SCOPES),
    ("interpret", MC, KERNEL_SCOPES + ("ga.fitness",)),
])
def test_generation_phases_carry_named_scopes(bc_dataset, backend, mc,
                                              scopes):
    cfg = GAConfig(pop_size=16, **mc,
                   backends=BackendPolicy(fitness="ref", generation=backend))
    problem = _problem(bc_dataset, cfg)
    state, _ = jax.eval_shape(engine.init_state, problem,
                              jax.random.PRNGKey(0))
    text = jax.jit(engine.generation).lower(problem, state).as_text(
        debug_info=True)
    missing = [s for s in scopes if f"/{s}/" not in text]
    assert not missing, missing
    others = set(REF_SCOPES + KERNEL_SCOPES) - set(scopes)
    assert not [s for s in others if f"/{s}/" in text]
    # the benchmark finds the megakernel by this name
    has_kernel = "@pop_generation_kernel(" in text
    assert has_kernel == (backend == "interpret")

