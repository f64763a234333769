"""The harness finds a configuration, traffic mix, driver and metric
that were added only as files plus BENCHMARK.json entries; and
bench/run.py refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import harness  # noqa: E402

TOY_DRIVER = '''
def setup(run):
    return {"rows": run.config["rows"]}

def window(run, state):
    run.begin_window()
    run.count("things", run.traffic["things"])
    run.end_window()
    return {"e2e": {"toy_per_s": 7.0}, "attempted": 3, "failed": 0}

def answers(run, state, out):
    return state["rows"]

def reference(run, state, out, control=False):
    return state["rows"] + (1 if control else 0)

def compare(run, state, got, ref):
    return {"toy_off": abs(got - ref)}
'''

TOY_METRIC = '''
def read(obs):
    return obs.counters["things"] * 2.0
'''


def _toy_root(tmp: Path) -> Path:
    bm = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
          "run_seconds": 1,
          "configs": [{"name": "toy_cfg", "source": "https://example.org",
                       "file": "bench/configs/toy_cfg.json", "reduced": [],
                       "why": "toy"}],
          "workloads": [{"name": "toy.cell", "config": "toy_cfg",
                         "traffic": "toy_mix", "chips": 1, "why": "toy"}],
          "end_to_end": [
              {"name": "toy_per_s", "unit": "1/s", "better": "higher",
               "bound": 0.05, "source": "host_clock"},
              {"name": "setup_s", "unit": "s", "better": "lower",
               "bound": 0.25, "source": "host_clock"}],
          "per_layer": [{"name": "toy_metric", "unit": "%",
                         "better": "higher", "source": "program_counter",
                         "layer": "toy", "moves": "toy_per_s"}]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    for sub in ("configs", "traffic", "drivers", "metrics"):
        (tmp / "bench" / sub).mkdir(parents=True)
    (tmp / "bench/configs/toy_cfg.json").write_text('{"rows": 5}')
    (tmp / "bench/traffic/toy_mix.json").write_text(
        '{"driver": "toy_driver", "things": 4, "checks": {"toy_off": 0}}')
    (tmp / "bench/drivers/toy_driver.py").write_text(TOY_DRIVER)
    (tmp / "bench/metrics/toy_metric.py").write_text(TOY_METRIC)
    return tmp


def _run(root: Path, trace: bool):
    run = harness.build_run(root, "toy.cell", 3, 1.0, trace,
                            time.perf_counter(), require_tpu=False)
    return harness.execute(run)


def test_cell_added_as_files_only(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda root: None)
    root = _toy_root(tmp_path)
    res = _run(root, trace=False)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"toy_per_s", "setup_s"}
    assert res["metrics"]["toy_per_s"] == {"value": 7.0, "unit": "1/s"}
    assert list(res)[-2] == "checks"
    assert res["checks"] == {"toy_off": {"value": 0, "limit": 0}}


def test_metric_added_as_files_only(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda root: None)
    root = _toy_root(tmp_path)
    bm = harness.load_benchmark(root)
    assert [m["name"] for m in harness.metrics_of(bm, "toy.cell",
                                                  "per_layer")] \
        == ["toy_metric"]
    reader = harness.load_module(harness.metric_file(root, "toy_metric"),
                                 "metric")
    run = harness.build_run(root, "toy.cell", 3, 1.0, False,
                            time.perf_counter(), require_tpu=False)
    drv = harness.load_module(harness.driver_file(root, "toy_driver"),
                              "driver")
    out = drv.window(run, drv.setup(run))
    assert reader.read(harness.Obs(run, out, None)) == 8.0


def test_seeds_are_31_bit_and_stable():
    s = harness.derive_seed(2 ** 31 + 12345, "wafer", 3)
    assert 0 <= s < 2 ** 31
    assert s == harness.derive_seed(2 ** 31 + 12345, "wafer", 3)
    assert s != harness.derive_seed(2 ** 31 + 12345, "wafer", 4)


def _bench_run(cwd: Path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "search.cardio",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_cpu():
    p = _bench_run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_run(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
