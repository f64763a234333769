"""Property sweep over the perf layer's analytic cost model (DESIGN.md
§11): every dispatch-registry entry must be priced, the counts must be
positive and monotone in every batch axis, the shared block_m heuristic
must match what the kernels themselves compute, and the MXU share of the
model must agree with the HLO dot-flops parser on small shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec import AdcSpec
from repro.kernels import dispatch, envelope
from repro.launch import analysis
from repro.perf import Workload, cost_model, shape_class, workload_of
from repro.perf.autotune import _tuning_operands
from tests.hypothesis_compat import given, settings, st

# one representative workload per registered entry — batch-like axes > 1
# wherever the entry has them, so the monotonicity sweep exercises them
WORKLOADS = {
    "adc_quantize": Workload("adc_quantize", m=32, c=4, bits=3),
    "adc_quantize_population":
        Workload("adc_quantize_population", m=32, c=4, bits=3, p=3),
    "mc_eval": Workload("mc_eval", m=32, c=4, bits=3, s=3),
    "mc_eval_population":
        Workload("mc_eval_population", m=32, c=4, bits=3, p=3, s=2),
    "mc_eval_cal": Workload("mc_eval_cal", m=32, c=4, bits=3, s=3),
    "mc_eval_cal_population":
        Workload("mc_eval_cal_population", m=32, c=4, bits=3, p=3, s=2),
    "bespoke_mlp": Workload("bespoke_mlp", m=32, c=4, bits=3, h=5, o=3),
    "bespoke_svm": Workload("bespoke_svm", m=32, c=4, bits=3, o=3),
    "classifier_bank_mlp":
        Workload("classifier_bank_mlp", m=32, c=4, bits=3, d=3, h=5, o=3),
    "classifier_bank_svm":
        Workload("classifier_bank_svm", m=32, c=4, bits=3, d=3, o=3),
}


def test_every_registry_entry_is_priced():
    """The registry and the perf layer must not drift: every registered
    entry has a representative workload here, a cost rule, a block_m
    heuristic, and a tuning-operand builder whose shapes round-trip
    through workload_of."""
    assert set(WORKLOADS) == set(dispatch.entries())
    for name in dispatch.entries():
        w = WORKLOADS[name]
        assert cost_model.cost(w).flops > 0
        assert cost_model.heuristic_block_m(w) >= 8
        operands, _spec = _tuning_operands(w)
        x, tables, *weights = operands
        got = workload_of(name, tuple(x.shape), tuple(tables.shape),
                          tuple(tuple(wt.shape) for wt in weights), w.bits)
        assert got == w, f"{name}: operand shapes round-trip to {got}"


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_counts_positive(name):
    c = cost_model.cost(WORKLOADS[name])
    assert c.flops > 0 and c.hbm_bytes > 0 and c.vmem_bytes > 0
    assert c.dot_flops >= 0 and c.grid_steps >= 1
    assert c.arithmetic_intensity > 0


@pytest.mark.parametrize("name", sorted(WORKLOADS))
@pytest.mark.parametrize("axis", ["m", "p", "s", "d"])
def test_counts_monotone_in_batch_axes(name, axis):
    """Growing any batch axis never shrinks work or traffic."""
    w = WORKLOADS[name]
    lo = cost_model.cost(w)
    for factor in (2, 5, 16):
        hi = cost_model.cost(w.replace(**{axis: getattr(w, axis) * factor}))
        assert hi.flops >= lo.flops
        assert hi.hbm_bytes >= lo.hbm_bytes
        assert hi.grid_steps >= lo.grid_steps
        lo = hi


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_heuristic_matches_kernel_families(name):
    """cost_model.heuristic_block_m delegates to the same helpers the
    kernels call, and every result respects the envelope contract:
    8-aligned (or M-capped), within [8, 4096]."""
    w = WORKLOADS[name]
    bm = cost_model.heuristic_block_m(w)
    assert 8 <= bm <= 4096
    assert bm == w.m or bm % 8 == 0
    big = cost_model.heuristic_block_m(w.replace(m=1 << 20))
    assert big % 8 == 0 and big <= 4096
    n = w.levels
    resident = {
        "adc_quantize": w.c * n + 2 * w.c,
        "adc_quantize_population": w.c * n + 2 * w.c,
        "mc_eval": 3 * w.c * n + 2 * w.c,
        "mc_eval_population": 3 * w.c * n + 2 * w.c,
        "mc_eval_cal": 4 * w.c * n + 2 * w.c,
        "mc_eval_cal_population": 4 * w.c * n + 2 * w.c,
        "bespoke_mlp": w.c * n + w.c * w.h + w.h + w.h * w.o + w.o + 2 * w.c,
        "classifier_bank_mlp":
            w.c * n + w.c * w.h + w.h + w.h * w.o + w.o + 2 * w.c,
        "bespoke_svm": w.c * n + w.c * w.o + w.o + 2 * w.c,
        "classifier_bank_svm": w.c * n + w.c * w.o + w.o + 2 * w.c,
    }[name]
    assert bm == envelope.auto_block_m(w.m, w.c, resident)


@pytest.mark.parametrize("name", ["bespoke_mlp", "bespoke_svm",
                                  "classifier_bank_mlp",
                                  "classifier_bank_svm"])
def test_dot_flops_agree_with_hlo_parser(name):
    """The model's MXU share equals what the HLO dot-flops parser counts
    on the jitted jnp oracle at the same shapes (the parser sees only
    dots, so this isolates exactly the Cost.dot_flops term)."""
    w = WORKLOADS[name]
    operands, spec = _tuning_operands(w)
    entry = dispatch.get(name)
    x, tables, *weights = operands
    text = (jax.jit(lambda *a: entry.oracle(*a, spec=spec))
            .lower(x, tables, *weights).compile().as_text())
    stats = analysis.hlo_stats(text)
    if stats.dot_ops == 0:
        pytest.skip("backend folded every dot at these shapes")
    want = cost_model.cost(w).dot_flops
    np.testing.assert_allclose(stats.flops, want, rtol=0.05)


def test_vpu_entries_have_no_dot_flops():
    for name in ("adc_quantize", "adc_quantize_population", "mc_eval",
                 "mc_eval_population"):
        assert cost_model.cost(WORKLOADS[name]).dot_flops == 0.0


def test_roofline_record_shape():
    """roofline_estimate emits the benchmarks/roofline.py record keys,
    a structurally-zero collective term (single chip), and a fraction in
    (0, 1]."""
    for name in dispatch.entries():
        r = cost_model.roofline_estimate(WORKLOADS[name], backend="tpu")
        for key in ("compute_s", "memory_s", "collective_s", "dominant",
                    "model_flops_global", "useful_flops_ratio",
                    "roofline_fraction", "estimated_s", "cost"):
            assert key in r, f"{name}: missing {key}"
        assert r["collective_s"] == 0.0
        assert r["dominant"] in ("compute", "memory", "overhead")
        assert 0.0 < r["roofline_fraction"] <= 1.0
        assert r["estimated_s"] >= max(r["compute_s"], r["memory_s"])


def test_machine_model_lookup():
    assert cost_model.machine_model("tpu").name == "tpu-v5e"
    with pytest.raises(ValueError, match="no machine peaks"):
        cost_model.machine_model("no-such-backend")
    active = cost_model.machine_model()
    assert active.peak_flops > 0 and active.hbm_bw > 0


def test_shape_class_buckets_batch_axes_only():
    """Neighbouring batch sizes share a tuned choice; structural extents
    do not."""
    w = Workload("adc_quantize", m=33, c=4, bits=3)
    assert shape_class(w) == shape_class(w.replace(m=64))
    assert shape_class(w) != shape_class(w.replace(m=65))
    assert shape_class(w) != shape_class(w.replace(c=5))
    assert shape_class(w) != shape_class(w.replace(bits=4))


@settings(max_examples=25, deadline=None)
@given(m=st.integers(1, 2048), c=st.integers(1, 64),
       bits=st.integers(1, 6), p=st.integers(1, 16),
       s=st.integers(1, 16), factor=st.integers(2, 8))
def test_property_costs_positive_and_monotone(m, c, bits, p, s, factor):
    """Hypothesis sweep: positivity + monotonicity hold across the whole
    envelope, not just the fixture shapes."""
    for name in ("adc_quantize_population", "mc_eval_population"):
        w = Workload(name, m=m, c=c, bits=bits, p=p, s=s)
        base = cost_model.cost(w)
        assert base.flops > 0 and base.hbm_bytes > 0
        for axis in ("m", "p", "s"):
            grown = cost_model.cost(
                w.replace(**{axis: getattr(w, axis) * factor}))
            assert grown.flops >= base.flops
            assert grown.hbm_bytes >= base.hbm_bytes


@settings(max_examples=25, deadline=None)
@given(m=st.integers(1, 1 << 16), c=st.integers(1, 512),
       bits=st.integers(1, 6))
def test_property_heuristic_is_valid_tile(m, c, bits):
    w = Workload("adc_quantize", m=m, c=c, bits=bits)
    bm = cost_model.heuristic_block_m(w)
    assert 1 <= bm <= max(m, 8)
    assert bm <= 4096
    assert bm == m or bm % 8 == 0


def test_spec_of_workload_consistency():
    """The envelope predicate the registry applies and the perf layer's
    pricing agree on what is representable: inside-envelope workloads
    always price; the pricing itself never consults the envelope."""
    spec = AdcSpec(bits=3)
    for name in dispatch.entries():
        res = dispatch.resolve(name, spec, 4, interpret=True,
                               workload=WORKLOADS[name])
        assert res.path == "kernel"
        assert res.block_m_source in ("tuned", "heuristic")


def test_tuning_operands_are_deterministic():
    w = WORKLOADS["bespoke_mlp"]
    a, _ = _tuning_operands(w, seed=7)
    b, _ = _tuning_operands(w, seed=7)
    for xa, xb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
