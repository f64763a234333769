"""Compile-only rehearsal: every dispatch-registry kernel, compiled by the
TPU compiler for a described (not attached) v5e chip at the widest
tabular spec's shapes (cardio: C = 21, M = 1490 training rows; P = 32,
S = 256, D = 8; bits 4 and 6). Interpret mode cannot see what Mosaic
refuses — block shapes that break the (8, 128) tiling rule, VMEM
overflow — so these tests guard the chip path without a chip.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.spec import AdcSpec
from repro.kernels import dispatch

C, M, P, S, D, H, O = 21, 1490, 32, 256, 8, 5, 3
ENTRIES = ("adc_quantize", "adc_quantize_population", "bespoke_mlp",
           "bespoke_svm", "classifier_bank_mlp", "classifier_bank_svm",
           "mc_eval", "mc_eval_cal", "mc_eval_cal_population",
           "mc_eval_population")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:          # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _operand_shapes(name, n, sharding):
    """The registry operand order of each entry (x, tables, *weights)."""
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    mc_rows = (sds(S, C), sds(S, C))
    return {
        "adc_quantize": (sds(M, C), sds(C, n)),
        "adc_quantize_population": (sds(M, C), sds(P, C, n)),
        "mc_eval": (sds(M, C), sds(S, C, n), sds(S, C, n), sds(C, n))
        + mc_rows,
        "mc_eval_population": (sds(M, C), sds(P, S, C, n), sds(P, S, C, n),
                               sds(C, n)) + mc_rows,
        "mc_eval_cal": (sds(M, C), sds(S, C, n), sds(S, C, n),
                        sds(S, C, n)) + mc_rows,
        "mc_eval_cal_population": (sds(M, C), sds(P, S, C, n),
                                   sds(P, S, C, n), sds(P, S, C, n))
        + mc_rows,
        "bespoke_mlp": (sds(M, C), sds(C, n), sds(C, H), sds(H), sds(H, O),
                        sds(O)),
        "bespoke_svm": (sds(M, C), sds(C, n), sds(C, O), sds(O)),
        "classifier_bank_mlp": (sds(M, C), sds(D, C, n), sds(D, C, H),
                                sds(D, H), sds(D, H, O), sds(D, O)),
        "classifier_bank_svm": (sds(M, C), sds(D, C, n), sds(D, C, O),
                                sds(D, O)),
    }[name]


def test_entry_list_is_the_registry():
    assert tuple(sorted(ENTRIES)) == dispatch.entries()


def test_bank_pool_mesh_takes_any_survivor_set(topo):
    """The serving pool re-meshes over the devices that survive a loss;
    3 of a 2x2 v5e no longer fill the torus, and the mesh must still be
    built, over exactly those devices in order."""
    from repro.distributed import elastic
    survivors = list(topo.devices[1:])
    mesh = elastic.bank_pool_mesh(survivors)
    assert mesh.devices.shape == (len(survivors), 1)
    assert list(mesh.devices.ravel()) == survivors


@pytest.mark.parametrize("bits", [4, 6])
@pytest.mark.parametrize("name", ENTRIES)
def test_entry_compiles_for_v5e(name, bits, one_chip):
    spec = AdcSpec(bits=bits)
    entry = dispatch.get(name)
    args = _operand_shapes(name, 2 ** bits, one_chip)
    fn = lambda *a: entry.kernel(*a, spec=spec, interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: no Mosaic kernel in the compiled program")


@pytest.mark.parametrize("path", ["seed", "draws"])
def test_mc_operands_compile_for_v5e(path, one_chip):
    """The robustness call's operand program (``compiled_mc_operands``)
    at the robust benchmark's shapes (D = 8, S = 1,024, bits 4), with the
    wafer's key or its draws as data."""
    from repro.core import nonideal
    s, n = 1024, 16

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    masks = sds((D, C, n), jnp.int32)
    kw = dict(spec=AdcSpec(bits=4), sigma_offset=0.5, sigma_range=0.01,
              fault_rate=0.01)
    if path == "seed":
        lowered = nonideal._compiled_mc_operands.lower(
            masks, sds((2,), jnp.uint32), None, samples=s, **kw)
    else:
        draws = nonideal.Draws(sds((s, C, n - 1)), sds((s, C, n - 1)),
                               sds((s, C, n - 1), jnp.bool_),
                               sds((s, C, 2)))
        lowered = nonideal._compiled_mc_operands.lower(
            masks, None, draws, samples=None, **kw)
    shapes = [o.shape for o in lowered.out_info]
    assert shapes == [(D, s, C, n), (D, s, C, n), (C, n), (s, C), (s, C)]
    lowered.compile()
