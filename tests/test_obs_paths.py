"""The spans the search generation and the robustness call open
(DESIGN.md §16), on a tiny search and a tiny robustness analysis."""
import collections

import numpy as np
import pytest

from repro import obs
from repro.core import deploy, search
from repro.core.nonideal import NonIdealSpec
from repro.data import tabular

SEEDS = tabular.make_dataset("seeds")
SIZES = (7, 4, 3)
POP, GENS = 4, 2
GEN_CHILDREN = {"nsga2.variation", "search.evaluate", "nsga2.survival"}
EVAL_CHILDREN = {"search.init", "search.launch", "search.fetch",
                 "search.areas"}
CALL_CHILDREN = ("deploy.mc_operands", "deploy.mc_launch", "deploy.rescore",
                 "deploy.report")


def _children(recs):
    kids = collections.defaultdict(list)
    for r in recs:
        kids[r.parent_id].append(r)
    return kids


@pytest.fixture(scope="module")
def searched():
    """One search's records, counters and trained front."""
    obs.reset()
    cfg = search.SearchConfig(bits=2, pop_size=POP, generations=GENS,
                              train_steps=5)
    out = search.run_search(SEEDS, SIZES, cfg, return_trained=True)
    recs = [r for r in obs.records() if r.name != "python.gc"]
    return {"cfg": cfg, "out": out, "records": recs,
            "counters": obs.counters()}


def test_one_generation_span_per_generation(searched):
    recs = searched["records"]
    (run,) = [r for r in recs if r.name == "search.run"]
    assert run.args == {"pop": POP, "generations": GENS}
    kids = _children(recs)
    gens = [r for r in kids[run.id] if r.name == "nsga2.generation"]
    assert len(gens) == GENS
    assert [r.name for r in kids[run.id]].count("nsga2.initial") == 1
    for g in gens:
        names = [r.name for r in kids[g.id]]
        assert set(names) == GEN_CHILDREN and len(names) == 3
        (ev,) = [r for r in kids[g.id] if r.name == "search.evaluate"]
        assert EVAL_CHILDREN <= {r.name for r in kids[ev.id]}
        assert all(g.t0_ns <= r.t0_ns <= r.t1_ns <= g.t1_ns
                   for r in kids[g.id])


def test_evaluations_count_every_individual(searched):
    recs = searched["records"]
    evals = [r for r in recs if r.name == "search.evaluate"]
    # the initial population and every generation's offspring; the
    # front's re-train is not an evaluation
    assert len(evals) == GENS + 1
    assert sum(r.args["individuals"] for r in evals) == POP * (GENS + 1)
    for r in evals:
        assert 1 <= r.args["unique"] <= r.args["individuals"]
        assert r.args["unique"] <= r.args["lanes"] <= r.args["individuals"]
    c = searched["counters"]
    assert c["search.individuals"] == POP * (GENS + 1)
    assert c["search.lanes"] == sum(r.args["lanes"] for r in evals)


def test_dedup_span_and_lanes_on_a_population_of_duplicates():
    obs.reset()
    cfg = search.SearchConfig(bits=2, pop_size=POP, generations=0,
                              train_steps=5)
    glen = search.genome_len(SIZES[0], cfg.bits)
    rng = np.random.default_rng(0)
    pop = (rng.random((POP, glen)) < 0.5).astype(np.uint8)
    pop[1:] = pop[0]                                    # one unique genome
    fit = search.make_eval_fn(SEEDS, SIZES, cfg)(pop)
    assert (fit == fit[0]).all()
    (ev,) = [r for r in obs.records() if r.name == "search.evaluate"]
    assert ev.args == {"individuals": POP, "unique": 1, "lanes": 1}
    (dd,) = [r for r in obs.records() if r.name == "search.dedup"]
    assert dd.parent_id == ev.id
    assert obs.counters() == {"search.individuals": POP, "search.lanes": 1}


def test_robustness_call_has_its_four_children(searched):
    cfg = searched["cfg"]
    pg, _, _, trained = searched["out"]
    designs = deploy.export_front(pg, SEEDS, SIZES, cfg, trained=trained)
    obs.reset()
    for seed in (1, 2):
        deploy.evaluate_robustness(designs, NonIdealSpec(sigma_offset=0.5,
                                                         seed=seed),
                                   SEEDS["x_test"], SEEDS["y_test"],
                                   samples=3)
    recs = [r for r in obs.records() if r.name != "python.gc"]
    kids = _children(recs)
    calls = [r for r in recs if r.name == "deploy.evaluate_robustness"]
    assert len(calls) == 2
    for call in calls:
        assert call.parent_id is None
        assert call.args == {"designs": len(designs), "samples": 3}
        assert [r.name for r in kids[call.id]] == list(CALL_CHILDREN)
        assert all(call.t0_ns <= r.t0_ns <= r.t1_ns <= call.t1_ns
                   for r in kids[call.id])
    for name in CALL_CHILDREN:
        assert sum(r.name == name for r in recs) == 2
    assert obs.counters() == {"deploy.instances": 2 * len(designs) * 3}
