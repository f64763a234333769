"""Closed-loop co-search: whole NSGA-II searches back to back through
``core.search.run_search`` (batched engine, dedup as shipped), each
search's initial population drawn from ``--seed`` and the search index.

Traffic parameters: ``pop``, ``generations``, ``train_steps``, ``lr``,
``sample`` (individuals the check re-trains), ``trace_seconds``.

Delivered individuals: the initial population and every generation's
offspring, ``pop`` each, counted as the fitness function returns them; a
search cut by the end of the window counts the generations it
completed. The window ends at the first generation that completes after
``--seconds``, so the rate is taken over all the work and all the time.

Every population the search evaluates in the window, and the fitness
returned for it, is recorded as ``run_search``'s fitness function hands
it back: the initial population and every generation's offspring,
whether selection keeps them or not. The check re-trains a sample of
them, drawn from the seed after the window, with the plain reference and
compares correct test rows.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench.lib import data as data_lib
from bench.lib import refs


class StopWindow(Exception):
    """Raised from the log callback when the window is over."""


def _cfg(run):
    from repro.core.search import SearchConfig
    c, t = run.config, run.traffic
    return SearchConfig(bits=c["adc_bits"], mode=c["adc_mode"],
                        weight_bits=c["weight_bits"], model=c["model"],
                        pop_size=t["pop"], generations=t["generations"],
                        train_steps=t["train_steps"], lr=t["lr"],
                        engine="batched", seed=run.seed_for("search"))


def initial_population(seed: int, pop: int, glen: int) -> np.ndarray:
    """Random bits with the full (unpruned) design first, as NSGA-II's
    own initial draw."""
    rng = np.random.default_rng(seed)
    p = (rng.random((pop, glen)) < 0.5).astype(np.uint8)
    p[0] = 1
    return p


def setup(run):
    from repro.core import search
    c = run.config
    sizes = (c["channels"], c["hidden"], c["classes"])
    data = data_lib.for_config(c, run.seed_for("data"))
    cfg = _cfg(run)
    glen = search.genome_len(sizes[0], cfg.bits)
    # every power-of-two dedup bucket the window can reach: b unique
    # genomes among pop (pop unique is the pass-through shape)
    eval_fn = search.make_eval_fn(data, sizes, cfg)
    rng = np.random.default_rng(run.seed_for("warmup"))
    b = 1
    while b <= cfg.pop_size:
        uniq = (rng.random((b, glen)) < 0.5).astype(np.uint8)
        eval_fn(uniq[np.arange(cfg.pop_size) % b])
        b *= 2
    return {"data": data, "sizes": sizes, "cfg": cfg, "glen": glen}


@contextlib.contextmanager
def _observed(search, record):
    """``run_search`` with its fitness function wrapped so that every
    population it evaluates, and the fitness returned, is recorded."""
    make = search.make_eval_fn

    def observed_make(*a, **kw):
        fn = make(*a, **kw)

        def eval_fn(pop):
            fit = fn(pop)
            record.append((np.array(pop, np.uint8), np.array(fit)))
            return fit
        return eval_fn

    search.make_eval_fn = observed_make
    try:
        yield
    finally:
        search.make_eval_fn = make


def window(run, state):
    from repro.core import search
    cfg, pop = state["cfg"], state["cfg"].pop_size
    evaluated = []                      # (genomes, fitness) per evaluation
    t0 = run.begin_window()
    t_end = t0 + run.seconds
    last = [t0]
    i = 0
    while True:
        init = initial_population(run.seed_for("init", i), pop,
                                  state["glen"])
        gens = [0]

        def log(g, p, fit):
            now = time.perf_counter()
            run.spans.setdefault("generation", []).append(now - last[0])
            last[0] = now
            gens[0] += 1
            run.tracer.tick()
            if now >= t_end:
                raise StopWindow

        try:
            with run.span("run_search"), _observed(search, evaluated):
                search.run_search(state["data"], state["sizes"], cfg,
                                  log=log, init=init)
        except StopWindow:
            break
        finally:
            run.count("generations", gens[0])
        run.count("searches")
        i += 1
    elapsed = run.end_window(last[0])
    genomes = np.concatenate([g for g, _ in evaluated])
    fitness = np.concatenate([f for _, f in evaluated])
    delivered = len(genomes)
    run.count("individuals", delivered)
    run.counters["train_rows"] = len(state["data"]["y_train"])
    run.counters["test_rows"] = len(state["data"]["y_test"])
    rng = np.random.default_rng(run.seed_for("sample"))
    pick = rng.choice(delivered, min(run.traffic["sample"], delivered),
                      replace=False)
    sample = [(genomes[j], float(fitness[j, 0])) for j in pick]
    return {"e2e": {"search_ind_per_s": delivered / elapsed},
            "attempted": delivered, "failed": 0, "sample": sample}


def answers(run, state, out):
    n = len(state["data"]["y_test"])
    return np.array([int(round((1.0 - f) * n)) for _, f in out["sample"]])


def reference(run, state, out, control=False):
    """Correct test rows of the sampled genomes, re-trained by the plain
    reference (float32 at HIGHEST; the control at HIGH, three bf16
    passes)."""
    import jax
    import jax.numpy as jnp
    c, t, d = run.config, run.traffic, state["data"]
    sizes = state["sizes"]
    luts, dps = [], []
    for g, _ in out["sample"]:
        mask, dp = refs.decode_genome(g, sizes[0], c["adc_bits"])
        luts.append(refs.value_lut(mask, c["adc_bits"]))
        dps.append(dp)
    params = refs.init_mlp(state["cfg"].seed, sizes)
    precision = "high" if control else refs.HIGHEST

    def one(lut, dp, x_tr, y_tr, x_te, y_te, params):
        return refs.qat_correct(
            refs.adc(x_tr, lut), y_tr, refs.adc(x_te, lut), y_te, dp,
            params, steps=t["train_steps"], lr=t["lr"],
            weight_bits=c["weight_bits"], classes=sizes[2],
            precision=precision)

    fn = jax.jit(jax.vmap(one, in_axes=(0, 0) + (None,) * 5))
    return np.asarray(fn(jnp.asarray(np.stack(luts)),
                         jnp.asarray(dps, jnp.float32), d["x_train"],
                         d["y_train"], d["x_test"], d["y_test"], params))


def compare(run, state, got, ref):
    return {"fitness_rows_off": int(np.max(np.abs(got - ref)))}
