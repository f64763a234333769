"""Closed-loop yield analysis: ``repro.api.evaluate_robustness`` called
back to back on a bank of designs made at set-up, S instances a call
over the whole test set, each call on a fresh wafer (a ``NonIdealSpec``
seeded from ``--seed`` and the call index).

Traffic parameters: ``designs``, ``samples``, ``sigma_offset``,
``sigma_range``, ``fault_rate``, ``sample_calls`` (calls the check
repeats with the reference), ``trace_seconds``.

Evaluated instances: designs x samples per completed call. The window
ends at the first call that completes after ``--seconds``.

The check recomputes every instance accuracy of a seeded sample of the
window's calls with the plain reference (the perturbed tree walk of each
instance, then the float32 forward) and compares correct test rows.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import data as data_lib
from bench.lib import designs as designs_lib
from bench.lib import refs


def _nonideal(run, i):
    from repro.core.nonideal import NonIdealSpec
    t = run.traffic
    return NonIdealSpec(sigma_offset=t["sigma_offset"],
                        sigma_range=t["sigma_range"],
                        fault_rate=t["fault_rate"],
                        seed=run.seed_for("wafer", i))


def setup(run):
    from repro import api
    c, t = run.config, run.traffic
    data = data_lib.for_config(c, run.seed_for("data"))
    designs, masks, weights = designs_lib.bank(run.seed_for("designs"), c,
                                               t["designs"])
    api.evaluate_robustness(designs, _nonideal(run, -1), data["x_test"],
                            data["y_test"], samples=t["samples"])
    return {"data": data, "designs": designs, "masks": masks,
            "weights": weights}


def window(run, state):
    from repro import api
    t, d = run.traffic, state["data"]
    n_inst = t["designs"] * t["samples"]
    rng = np.random.default_rng(run.seed_for("sample"))
    keep, seen = [], 0                 # reservoir of (call, accuracies)
    t0 = run.begin_window()
    calls, now = 0, t0
    while now < t0 + run.seconds:
        with run.span("evaluate_robustness"):
            rep = api.evaluate_robustness(state["designs"],
                                          _nonideal(run, calls),
                                          d["x_test"], d["y_test"],
                                          samples=t["samples"])
        now = time.perf_counter()
        acc = np.array([r["instance_accuracies"] for r in rep["designs"]])
        seen += 1
        if len(keep) < t["sample_calls"]:
            keep.append((calls, acc))
        else:
            j = int(rng.integers(0, seen))
            if j < t["sample_calls"]:
                keep[j] = (calls, acc)
        calls += 1
        run.tracer.tick()
    elapsed = run.end_window(now)
    run.count("calls", calls)
    run.count("instances", calls * n_inst)
    run.counters["test_rows"] = len(d["y_test"])
    return {"e2e": {"mc_evals_per_s": calls * n_inst / elapsed},
            "attempted": calls * n_inst, "failed": 0, "sample": keep}


def answers(run, state, out):
    n = len(state["data"]["y_test"])
    return np.stack([np.rint(acc * n).astype(np.int64)
                     for _, acc in out["sample"]])


def reference(run, state, out, control=False):
    """(calls, D, S) correct rows of every sampled instance. The control
    computes the instances' code positions in bfloat16."""
    import jax
    import jax.numpy as jnp
    c, t, d = run.config, run.traffic, state["data"]
    x, y = jnp.asarray(d["x_test"]), jnp.asarray(d["y_test"])
    dtype = jnp.bfloat16 if control else jnp.float32
    fn = jax.jit(lambda x, y, mask, w, wafer: refs.mc_correct(
        x, y, mask, w, wafer, bits=c["adc_bits"],
        sigma_offset=t["sigma_offset"], sigma_range=t["sigma_range"],
        fault_rate=t["fault_rate"], dtype=dtype))
    out_calls = []
    for call, _ in out["sample"]:
        wafer = refs.draw_wafer(_nonideal(run, call).seed, c["adc_bits"],
                                c["channels"], t["samples"])
        per = [np.asarray(fn(x, y, jnp.asarray(state["masks"][k]),
                             tuple(jnp.asarray(w[k])
                                   for w in state["weights"]), wafer))
               for k in range(t["designs"])]
        out_calls.append(np.stack(per))
    return np.stack(out_calls).astype(np.int64)


def compare(run, state, got, ref):
    return {"instance_rows_off": int(np.max(np.abs(got - ref)))}
