"""chip_smoke.py refuses to pass anywhere but on a TPU with every main-path
entry compiled: no result line off the chip, and a resolution to the jnp
oracle or to interpret mode is a failure."""
import importlib.util
from pathlib import Path

import pytest

from repro.kernels import dispatch
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys, monkeypatch):
    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "needs a TPU" in captured.err


@pytest.mark.parametrize("res", [
    dispatch.Resolution("classifier_bank_mlp", "oracle", None, False,
                        "auto off-TPU"),
    dispatch.Resolution("mc_eval_population", "kernel", True, False,
                        "explicit interpret=True"),
], ids=["oracle", "interpret"])
def test_main_path_must_run_compiled(smoke, res):
    with pytest.raises(smoke.SmokeFailure, match="not the compiled kernel"):
        smoke.check_compiled(res)


def test_compiled_kernel_passes(smoke):
    smoke.check_compiled(dispatch.Resolution(
        "adc_quantize_population", "kernel", False, False,
        "auto: TPU backend, compiled kernel"))


def test_entry_workload_sets_only_the_entry_axes(smoke):
    w = smoke.entry_workload("mc_eval_population", 1490, 21, 4, p=32, s=256,
                             d=8, h=5, o=3)
    assert (w.p, w.s, w.d, w.h, w.o) == (32, 256, 1, 0, 0)
    w = smoke.entry_workload("classifier_bank_mlp", 638, 21, 4, p=32, s=256,
                             d=8, h=5, o=3)
    assert (w.p, w.s, w.d, w.h, w.o) == (1, 1, 8, 5, 3)
