"""Device traces: capture a window with the JAX profiler, extract the
events the benchmark reads, and reduce them to busy time, kernel time and
the breakdown.

Extraction keeps, per device plane ``/device:TPU:<i>``, the ``XLA Ops``
line (one event per executed HLO instruction, named by its HLO text, so
the output shape is in the name) and the ``XLA Modules`` line (one event
per program run); and, from ``/host:CPU``, the events of the threads that
ran the benchmark's own annotations or dispatched to the device. Times
are nanoseconds on the profiler's clock; host and device clocks agree
only to about a millisecond, so short gaps may be named by a neighbour.

The reduction is pure and works on that extracted form, which is what the
tests check against a trace recorded on the chip:

* busy: the length of the union of a device's op intervals;
* kernel time: the summed durations of the ops whose instruction name
  starts with one of a kernel's names;
* top device ops: time per program/instruction, container ops (while,
  conditional, call) left out since their bodies are counted;
* idle gaps: each gap between merged busy intervals, named by the
  innermost host event of the benchmark's thread that covers the gap's
  middle, summed per name.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
CONTAINERS = ("while", "conditional", "call")
BENCH_SPAN_PREFIX = "bench."
# host threads kept for naming idle gaps: the benchmark's own, and those
# that dispatch to the device
HOST_THREAD_MARKS = (BENCH_SPAN_PREFIX, "PjitFunction", "DevicePut")


def instruction(op_name: str) -> str:
    """'%fusion.13 = s32[...] fusion(...)' -> 'fusion.13'."""
    head = op_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def output_dims(op_name: str) -> Optional[Tuple[int, ...]]:
    """Dimensions of an op's (first) output array, read from its HLO text."""
    m = re.search(r"=\s*\(?\s*[a-z0-9]+\[([0-9,]*)\]", op_name)
    if not m:
        return None
    return tuple(int(v) for v in m.group(1).split(",") if v)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of [start, end) intervals as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Sequence[Dict]) -> float:
    return sum(e - s for s, e in merge((o["start"], o["start"] + o["dur"])
                                       for o in ops))


def kernel_ops(ops: Sequence[Dict], names: Sequence[str]) -> List[Dict]:
    """The ops that are launches of a kernel known by ``names``."""
    return [o for o in ops if instruction(o["name"]).split(".")[0] in names]


def kernel_ns(ops: Sequence[Dict], names: Sequence[str]) -> float:
    return float(sum(o["dur"] for o in kernel_ops(ops, names)))


def _module_of(modules: Sequence[Dict], t: float) -> str:
    for m in modules:
        if m["start"] <= t < m["start"] + m["dur"]:
            return m["name"].split("(")[0]
    return "?"


def top_ops(ops: Sequence[Dict], modules: Sequence[Dict],
            k: int = 10) -> List[Tuple[str, float]]:
    """[(program/instruction, seconds)] of the k ops that took most time."""
    tot: Dict[str, float] = {}
    for o in ops:
        ins = instruction(o["name"])
        if ins.split(".")[0] in CONTAINERS:
            continue
        key = f"{_module_of(modules, o['start'])}/{ins}"
        tot[key] = tot.get(key, 0.0) + o["dur"]
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns / 1e9) for name, ns in best]


def idle_gaps(ops: Sequence[Dict], host: Sequence[Dict], k: int = 10,
              ) -> List[Tuple[str, float]]:
    """[(host activity, seconds)] of the device's idle gaps between its
    first and last op, each named by the innermost host event covering
    the gap's middle, summed per name, the k largest."""
    busy = merge((o["start"], o["start"] + o["dur"]) for o in ops)
    tot: Dict[str, float] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        mid = 0.5 * (e0 + s1)
        cover = [h for h in host if h["start"] <= mid < h["start"] + h["dur"]]
        name = (min(cover, key=lambda h: h["dur"])["name"] if cover
                else "no host event")
        tot[name] = tot.get(name, 0.0) + (s1 - e0)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns / 1e9) for name, ns in best]


def extract(xplane_path: str, devices: int = 1) -> Dict:
    """The events the reduction reads, from one ``.xplane.pb`` file:
    ``{"devices": [{"ops": [...], "modules": [...]}, ...], "host": [...]}``
    with each event ``{"name", "start", "dur"}`` in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    dev: Dict[int, Dict] = {}
    host: List[Dict] = []
    ev = lambda e: {"name": e.name, "start": float(e.start_ns),
                    "dur": float(e.duration_ns)}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < devices:
            d = dev.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d["ops"] = [ev(e) for e in line.events]
                elif line.name == "XLA Modules":
                    d["modules"] = [ev(e) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [ev(e) for e in line.events]
                if any(e["name"].startswith(HOST_THREAD_MARKS)
                       for e in events):
                    host.extend(events)
    return {"devices": [dev[i] for i in sorted(dev)], "host": host}


def summarize(extracted: Dict, window_s: float) -> Dict:
    """busy_s (mean over devices), window_s and the breakdown."""
    devs = extracted["devices"]
    busy = [busy_ns(d["ops"]) / 1e9 for d in devs]
    ops0 = devs[0]["ops"] if devs else []
    mods0 = devs[0]["modules"] if devs else []
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window_s,
        "breakdown": {"device_ops": [list(t) for t in top_ops(ops0, mods0)],
                      "idle_gaps": [list(t) for t in
                                    idle_gaps(ops0, extracted["host"])]},
    }


class Tracer:
    """Profiles the first ``seconds`` of a window. ``tick()`` at natural
    boundaries ends the trace once that time has passed; ``close()`` ends
    it in any case and reads it."""

    def __init__(self, enabled: bool, seconds: float, devices: int = 1):
        self.enabled = enabled
        self.seconds = float(seconds)
        self.devices = devices
        self._dir: Optional[str] = None
        self._t0: Optional[float] = None
        self.window_s: Optional[float] = None
        self.stop_s = 0.0           # spent ending the trace
        self.extracted: Optional[Dict] = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._t0 = time.perf_counter()

    @property
    def running(self) -> bool:
        return self._t0 is not None and self.window_s is None

    def tick(self) -> None:
        if self.running and time.perf_counter() - self._t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if not self.running:
            return
        import jax
        t = time.perf_counter()
        self.window_s = t - self._t0
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t

    def close(self) -> Optional[Dict]:
        """Stop if still running, read the trace, delete its files, and
        return ``summarize``'s dict (None when disabled)."""
        if not self.enabled:
            return None
        self.stop()
        try:
            files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            self.extracted = extract(files[0], self.devices)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return summarize(self.extracted, self.window_s)


def annotate(name: str):
    """A host span on the profiler's clock (a no-op without a trace)."""
    import jax
    return jax.profiler.TraceAnnotation(BENCH_SPAN_PREFIX + name)
