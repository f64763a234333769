"""The harness: runs one cell once, as ``bench/run.py`` is asked to.

A cell is found by name in ``BENCHMARK.json``. Its configuration file,
its traffic file ``bench/traffic/<mix>.json``, the driver that the traffic
file names (``bench/drivers/<driver>.py``), the readers of its per-layer
metrics (``bench/metrics/<metric>.py``) and the work counts those readers
use (``bench/work/<kernel>.py``) are all found by name, so a new cell,
traffic mix, driver or metric is a new file plus entries in
``BENCHMARK.json``.

A driver module defines:

* ``setup(run) -> state``: build the system under test from the seed and
  warm every shape the window uses;
* ``window(run, state) -> dict``: the measured window, between
  ``run.begin_window()`` and ``run.end_window()``; returns the cell's
  end-to-end values under ``"e2e"``, and ``"attempted"``, ``"failed"``;
* ``answers(run, state, out)``: the program's answers that the check
  compares, after the window;
* ``reference(run, state, out, control=False)``: the plain reference's
  answers for the same items; ``control=True`` gives the control's,
  computed in the precision below the configuration's;
* ``compare(run, state, answers, reference) -> {name: value}``: the
  numbers held against the traffic file's ``"checks"`` limits.

A metric reader defines ``read(obs) -> float | None``; ``None`` leaves the
metric out of the result line.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class CellError(RuntimeError):
    """The cell cannot be run as asked."""


# ------------------------------------------------------------ finding by name
def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise CellError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, tag: str):
    if not path.is_file():
        raise CellError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Dict:
    if not path.is_file():
        raise CellError(f"missing file {path}")
    return json.loads(path.read_text())


def traffic_file(root: Path, mix: str) -> Path:
    return root / "bench" / "traffic" / f"{mix}.json"


def driver_file(root: Path, driver: str) -> Path:
    return root / "bench" / "drivers" / f"{driver}.py"


def metric_file(root: Path, metric: str) -> Path:
    return root / "bench" / "metrics" / f"{metric}.py"


def work_file(root: Path, kernel: str) -> Path:
    return root / "bench" / "work" / f"{kernel}.py"


def metrics_of(bm: Dict, cell: str, kind: str) -> List[Dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics: those that list
    it under ``workloads``, and those without the key (a per-layer metric
    without it belongs to every cell that reports the metric it moves)."""
    if kind == "end_to_end":
        return metrics_of_e2e(bm, cell)
    e2e = [m["name"] for m in metrics_of_e2e(bm, cell)]
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def metrics_of_e2e(bm: Dict, cell: str) -> List[Dict]:
    return [m for m in bm["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def derive_seed(seed: int, purpose: str, i: int = 0) -> int:
    """A 31-bit seed for one purpose of a run, from ``--seed``."""
    h = hashlib.sha256(f"{int(seed)}/{purpose}/{int(i)}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


# ------------------------------------------------------------------ the run
class CompileCounter:
    """Counts compilations, traces and persistent-cache hits, apart for
    set-up and for the window (jax.monitoring)."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self) -> None:
        import jax
        self.phase = "setup"
        self.counts = {p: dict.fromkeys(self.EVENTS.values(), 0)
                       for p in ("setup", "window", "after")}

        def on_event(name, *args, **kw):
            if name in self.EVENTS:
                self.counts[self.phase][self.EVENTS[name]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        jax.monitoring.register_event_listener(on_event)


class Run:
    """One run of one cell: what the driver reads, and what it records."""

    def __init__(self, *, root: Path, bm: Dict, cell: Dict, config: Dict,
                 traffic: Dict, seed: int, seconds: float, trace: bool,
                 peaks: Optional[Dict], t_start: float):
        from bench.lib.trace import Tracer
        self.root, self.bm, self.cell = root, bm, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.peaks, self.t_start = peaks, t_start
        self.counters: Dict[str, float] = {}
        self.spans: Dict[str, List[float]] = {}
        self.tracer = Tracer(trace, traffic.get("trace_seconds", 4.0),
                             devices=cell["chips"])
        self.compile_counter = CompileCounter()
        self.window_t0: Optional[float] = None
        self.window_s: Optional[float] = None

    def seed_for(self, purpose: str, i: int = 0) -> int:
        return derive_seed(self.seed, purpose, i)

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    @contextlib.contextmanager
    def span(self, name: str):
        """A host-clock span, also written into the trace when tracing."""
        from bench.lib.trace import annotate
        t0 = time.perf_counter()
        with annotate(name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def begin_window(self) -> float:
        self.compile_counter.phase = "window"
        self.tracer.start()
        self.window_t0 = time.perf_counter()
        return self.window_t0

    def end_window(self, t_end: Optional[float] = None) -> float:
        t = time.perf_counter() if t_end is None else t_end
        self.window_s = t - self.window_t0
        self.compile_counter.phase = "after"
        return self.window_s

    def work(self, kernel: str):
        return load_module(work_file(self.root, kernel), "work")


class Obs:
    """What a per-layer metric reader reads."""

    def __init__(self, run: Run, out: Dict, trace: Optional[Dict]):
        self.run, self.out, self.trace = run, out, trace
        self.config, self.traffic = run.config, run.traffic
        self.counters, self.spans = run.counters, run.spans
        self.peaks = run.peaks
        # the measured window without the time the profiler took to end
        # its trace inside it (seconds on a busy trace)
        self.window_s = (run.window_s - run.tracer.stop_s
                         if run.window_s else run.window_s)

    @property
    def device_ops(self) -> List[Dict]:
        ex = self.run.tracer.extracted
        return ex["devices"][0]["ops"] if ex and ex["devices"] else []

    def work(self, kernel: str):
        return self.run.work(kernel)


def enable_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, every program cached however small or quick, and no
    eviction (an eviction limit in the environment would need access-time
    files that entries copied in from elsewhere lack, and then no new
    entry is written)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def build_run(root: Path, workload: str, seed: int, seconds: float,
              trace: bool, t_start: float, *, require_tpu: bool = True,
              traffic_override: Optional[Dict] = None) -> Run:
    """Everything up to the driver's set-up: files, cache, device check."""
    bm = load_benchmark(root)
    cell = find(bm["workloads"], workload, "workload")
    cfg_entry = find(bm["configs"], cell["config"], "config")
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(traffic_file(root, cell["traffic"]))
    if traffic_override:
        traffic = {**traffic, **traffic_override}
    enable_cache(root)
    import jax
    devs = jax.devices()
    peaks = None
    if require_tpu:
        if devs[0].platform != "tpu":
            raise CellError(f"needs a TPU; JAX found {devs[0].platform} "
                            f"({devs[0].device_kind})")
        if len(devs) < cell["chips"]:
            raise CellError(f"cell {workload} needs {cell['chips']} chips, "
                            f"JAX found {len(devs)}")
        from bench.lib.peaks import peaks_for
        peaks = peaks_for(devs[0].device_kind)
    return Run(root=root, bm=bm, cell=cell, config=config, traffic=traffic,
               seed=seed, seconds=seconds, trace=trace, peaks=peaks,
               t_start=t_start)


def check_numbers(run: Run, driver, state, out) -> Dict[str, Dict]:
    """The program's answers against the plain reference, each number
    beside its limit from the traffic file."""
    got = driver.answers(run, state, out)
    ref = driver.reference(run, state, out, control=False)
    values = driver.compare(run, state, got, ref)
    limits = run.traffic["checks"]
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}


def execute(run: Run) -> Dict:
    """Set-up, window, memory, trace, check; returns the result dict."""
    import jax
    driver = load_module(driver_file(run.root, run.traffic["driver"]),
                         "driver")
    state = driver.setup(run)
    # what set-up made (data, the trace, the bank) stays alive all run:
    # keep it out of the collector's scans, so that its pauses in the
    # window are the program's own
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - run.t_start
    out = driver.window(run, state)
    used = jax.devices()[: run.cell["chips"]]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    t_window = time.perf_counter()
    trace = run.tracer.close()
    t_trace = time.perf_counter()
    checks = check_numbers(run, driver, state, out)
    t_check = time.perf_counter()
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    units = {m["name"]: m["unit"]
             for m in run.bm["end_to_end"] + run.bm["per_layer"]}
    metrics: Dict[str, Dict] = {}
    if run.trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        obs = Obs(run, out, trace)
        for m in metrics_of(run.bm, run.cell["name"], "per_layer"):
            reader = load_module(metric_file(run.root, m["name"]), "metric")
            v = reader.read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, **out["e2e"]}
        for m in metrics_of(run.bm, run.cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": units[m["name"]]}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if run.trace:
        result["breakdown"] = trace["breakdown"]
    result["checks"] = checks
    result["_diagnostics"] = {
        "compiles": run.compile_counter.counts,
        "seconds": {"setup": setup_s, "window": run.window_s,
                    "trace_stop": run.tracer.stop_s,
                    "trace": t_trace - t_window, "check": t_check - t_trace},
        "spans": run.spans}
    return result


def report(result: Dict, out=sys.stdout, err=sys.stderr) -> None:
    """Earlier lines, the checks as the last lines of stderr, and the
    result as the last line of stdout."""
    diag = result.pop("_diagnostics")
    counts = diag["compiles"]
    print("seconds: " + ", ".join(f"{k} {v:.3f}"
                                  for k, v in diag["seconds"].items()),
          file=out, flush=True)
    for name, v in sorted(diag["spans"].items()):
        print(f"span {name}: {len(v)} times, median {statistics.median(v):.4f}"
              f" s, longest {max(v):.4f} s", file=out, flush=True)
    for phase in ("setup", "window", "after"):
        c = counts[phase]
        print(f"compilations in {phase}: "
              f"{c['compiles'] - c['cache_hits']} fresh, {c['cache_hits']} "
              f"from the persistent cache ({c['traces']} traces)",
              file=out, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
