"""The block_m autotuner (repro/perf/autotune.py) and its dispatch-layer
integration (DESIGN.md §11): deterministic tables from fixed
measurements, JSON persistence round-trips, tuned resolutions are taken
and logged, and corrupt/stale tables degrade to the VMEM heuristic."""
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adc
from repro.core.spec import AdcSpec
from repro.kernels import dispatch
from repro.perf import Workload, autotune, cost_model, shape_class

W_ADC = Workload("adc_quantize", m=32, c=4, bits=3)
W_POP = Workload("adc_quantize_population", m=32, c=4, bits=3, p=2)


@pytest.fixture(autouse=True)
def _clean_policy():
    """Every test starts and ends with no tuned policy installed."""
    dispatch.set_tuned_policy(None)
    yield
    dispatch.reset_tuned_policy()


def _meas(prefer: int):
    """A deterministic measurement: ``prefer`` wins, everything else is
    monotone in the tile so the ranking is unambiguous."""
    return lambda entry, w, bm: 1.0 if bm == prefer else 10.0 + bm


def test_candidates_cover_heuristic_and_m():
    cands = autotune.candidate_block_ms(W_ADC)
    assert min(cost_model.heuristic_block_m(W_ADC), W_ADC.m) in cands
    assert min(W_ADC.m, 4096) in cands
    assert cands == tuple(sorted(set(cands)))
    big = Workload("adc_quantize", m=10000, c=4, bits=3)
    assert max(autotune.candidate_block_ms(big)) <= 4096


def test_tables_are_deterministic():
    """Same workloads + same measurements -> byte-identical JSON."""
    kw = dict(measure_fn=_meas(16), backend="cpu")
    a = autotune.tune([W_ADC, W_POP], **kw)
    b = autotune.tune([W_POP, W_ADC], **kw)   # order must not matter
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert a["entries"]["adc_quantize"][shape_class(W_ADC)]["block_m"] == 16


def test_tie_breaks_toward_smaller_tile():
    table = autotune.tune([W_ADC], measure_fn=lambda e, w, bm: 1.0,
                          backend="cpu")
    rec = table["entries"]["adc_quantize"][shape_class(W_ADC)]
    assert rec["block_m"] == min(autotune.candidate_block_ms(W_ADC))


def test_winner_never_loses_to_heuristic():
    """The heuristic tile is always a candidate, so the tuned pick's
    measured time is <= the heuristic's by construction."""
    rng_meas = lambda e, w, bm: float((bm * 2654435761) % 1000) + 1.0
    table = autotune.tune([W_ADC, W_POP], measure_fn=rng_meas,
                          backend="cpu")
    for entry in table["entries"].values():
        for rec in entry.values():
            assert rec["us"] <= rec["heuristic_us"]


def test_json_round_trip(tmp_path):
    p = tmp_path / "tuned.json"
    table = autotune.tune([W_ADC], measure_fn=_meas(8), backend="cpu")
    autotune.save_table(table, p)
    loaded = autotune.load_table(p)
    assert loaded == json.loads(json.dumps(table))
    # re-saving the loaded table is byte-stable
    autotune.save_table(loaded, p)
    assert autotune.load_table(p) == loaded


def test_dispatch_resolves_and_logs_tuned_choice(caplog):
    table = autotune.tune([W_ADC], measure_fn=_meas(16), backend="cpu")
    dispatch.set_tuned_policy(autotune.TablePolicy(table))
    spec = AdcSpec(bits=3)
    res = dispatch.resolve("adc_quantize", spec, 4, interpret=True,
                           workload=W_ADC)
    assert (res.block_m, res.block_m_source) == (16, "tuned")
    assert res.as_dict()["block_m"] == 16

    # ...and the executed path logs the tile with its provenance
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((32, 4)), jnp.float32)
    mask = adc.repair_mask(jnp.asarray(
        (rng.random((4, 8)) < 0.6).astype(np.int32)))
    dispatch._LOGGED.clear()
    with caplog.at_level(logging.INFO, logger="repro.kernels.dispatch"):
        dispatch.dispatch("adc_quantize", x, spec.value_table(mask),
                          spec=spec, interpret=True)
    text = "\n".join(r.getMessage() for r in caplog.records)
    assert "block_m=16:tuned" in text


def test_tuned_block_m_changes_speed_not_values():
    """The parity contract under tuning: any tuned tile returns bitwise
    the heuristic-tile result."""
    spec = AdcSpec(bits=3)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random((37, 4)), jnp.float32)
    mask = adc.repair_mask(jnp.asarray(
        (rng.random((4, 8)) < 0.6).astype(np.int32)))
    t = spec.value_table(mask)
    want = dispatch.dispatch("adc_quantize", x, t, spec=spec,
                             interpret=True)
    for bm in autotune.candidate_block_ms(Workload("adc_quantize", m=37,
                                                   c=4, bits=3)):
        table = autotune.tune([Workload("adc_quantize", m=37, c=4, bits=3)],
                              measure_fn=_meas(bm), backend="cpu")
        dispatch.set_tuned_policy(autotune.TablePolicy(table))
        got = dispatch.dispatch("adc_quantize", x, t, spec=spec,
                                interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dispatch_logs_the_heuristic_tile_it_used(caplog):
    """Without a tuned entry (e.g. a table tuned on another backend) the
    log names the VMEM-heuristic tile the kernel runs with."""
    dispatch.set_tuned_policy(None)
    spec = AdcSpec(bits=3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((32, 4)), jnp.float32)
    mask = adc.repair_mask(jnp.asarray(
        (rng.random((4, 8)) < 0.6).astype(np.int32)))
    dispatch._LOGGED.clear()
    with caplog.at_level(logging.INFO, logger="repro.kernels.dispatch"):
        dispatch.dispatch("adc_quantize", x, spec.value_table(mask),
                          spec=spec, interpret=True)
    want = cost_model.heuristic_block_m(
        Workload("adc_quantize", m=32, c=4, bits=3))
    assert f"block_m={want}:heuristic" in caplog.text


def test_unmatched_shape_class_falls_back_to_heuristic():
    table = autotune.tune([W_ADC], measure_fn=_meas(16), backend="cpu")
    dispatch.set_tuned_policy(autotune.TablePolicy(table))
    other = Workload("adc_quantize", m=4096, c=9, bits=3)
    res = dispatch.resolve("adc_quantize", AdcSpec(bits=3), 9,
                           interpret=True, workload=other)
    assert (res.block_m, res.block_m_source) == (None, "heuristic")


def test_corrupt_table_falls_back(tmp_path, caplog):
    p = tmp_path / "tuned.json"
    p.write_text("{not json")
    with caplog.at_level(logging.WARNING, logger="repro.perf.autotune"):
        assert autotune.load_table(p) is None
        assert autotune.load_policy(p) is None
    assert "corrupt" in caplog.text


def test_wrong_schema_and_version_fall_back(tmp_path):
    p = tmp_path / "tuned.json"
    p.write_text(json.dumps({"version": 999, "backend":
                             jax.default_backend(), "entries": {}}))
    assert autotune.load_table(p) is None
    p.write_text(json.dumps(["not", "a", "table"]))
    assert autotune.load_table(p) is None
    p.write_text(json.dumps({"version": autotune.TABLE_VERSION,
                             "backend": jax.default_backend()}))
    assert autotune.load_table(p) is None     # entries missing


def test_stale_backend_falls_back(tmp_path, caplog):
    """A table tuned on another machine's backend must not apply here."""
    p = tmp_path / "tuned.json"
    table = autotune.tune([W_ADC], measure_fn=_meas(16),
                          backend="definitely-not-this-backend")
    p.write_text(json.dumps(table))
    with caplog.at_level(logging.WARNING, logger="repro.perf.autotune"):
        assert autotune.load_table(p) is None
    assert "stale" in caplog.text
    # dispatch keeps working on the heuristic
    res = dispatch.resolve("adc_quantize", AdcSpec(bits=3), 4,
                           interpret=True, workload=W_ADC)
    assert (res.block_m, res.block_m_source) == (None, "heuristic")


def test_api_autotune_end_to_end(tmp_path):
    """repro.api.autotune tunes, persists, and activates in one call."""
    from repro import api
    p = tmp_path / "tuned.json"
    table = api.autotune([W_ADC], measure_fn=_meas(16), path=p,
                         backend=jax.default_backend())
    assert p.exists()
    # save_table reset the cached policy; point the default loader at our
    # table and confirm a fresh resolution picks it up
    dispatch.set_tuned_policy(autotune.load_policy(p))
    res = dispatch.resolve("adc_quantize", AdcSpec(bits=3), 4,
                           interpret=True, workload=W_ADC)
    assert (res.block_m, res.block_m_source) == (16, "tuned")
    assert table["entries"]["adc_quantize"][shape_class(W_ADC)]
