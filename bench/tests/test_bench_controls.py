"""Each cell's control fails its check and the program passes it, at a
size a test run holds: the plain reference computed in the precision
below the configuration's (search: matmuls at ``high``, three bfloat16
passes; robust: code positions in bfloat16) is
put in the program's place and compared with the reference at the
configuration's precision."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.lib import harness  # noqa: E402

SMALL = {
    "search.cardio": {"pop": 4, "generations": 2, "sample": 8},
    "robust.cardio": {"samples": 32},
    "search.whitewine": {"pop": 4, "generations": 2, "sample": 8},
    "robust.whitewine": {"samples": 32},
}


def _limits_module():
    spec = importlib.util.spec_from_file_location(
        "bench_limits", ROOT / "bench" / "limits.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_fails_and_program_passes(workload, monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda root: None)
    bm = harness.load_benchmark(ROOT)
    mix = harness.find(bm["workloads"], workload, "workload")["traffic"]
    checks = json.loads(harness.traffic_file(ROOT, mix).read_text())[
        "checks"]
    row = _limits_module().readings(workload, 2 ** 31 + 3, 1.0, True,
                                    require_tpu=False,
                                    traffic_override=SMALL[workload])
    for name, limit in checks.items():
        assert row["program"][name] <= limit, row
        assert row["control"][name] > limit, row
