"""Arithmetic shared by the per-layer metric readers in
``bench/metrics/``. A reader returns None where there is nothing to read,
never 0 for a share."""
from __future__ import annotations

from statistics import median
from typing import Callable, Optional, Sequence

from bench.lib import trace as trace_lib


def idle_share(obs) -> Optional[float]:
    """Percent of the traced window in which no op ran on the device."""
    t = obs.trace
    if not t or not t["window_s"] or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline(obs, names: Sequence[str],
             need: Callable[[tuple], dict]) -> Optional[float]:
    """Percent of the kernel's traced time that its least possible time
    fills: per launch the larger of operations over the bf16 peak and
    bytes over the HBM bandwidth, ``need(output_dims)`` giving the
    launch's {"flops", "bytes"}."""
    ops = trace_lib.kernel_ops(obs.device_ops, names)
    took = sum(o["dur"] for o in ops) / 1e9
    if not ops or took <= 0:
        return None
    least = 0.0
    for o in ops:
        w = need(trace_lib.output_dims(o["name"]))
        least += max(w["flops"] / obs.peaks["bf16_flops"],
                     w["bytes"] / obs.peaks["hbm_bytes_per_s"])
    return 100.0 * least / took


def mfu(obs, flops: float) -> Optional[float]:
    """Percent of the bf16 peak that ``flops`` over the window fills."""
    if not obs.window_s or flops <= 0:
        return None
    return 100.0 * flops / obs.window_s / obs.peaks["bf16_flops"]


def real_rows(padded: int, candidates: Sequence[int]) -> int:
    """The row count a launch padded to ``padded`` rows was asked for:
    the largest candidate that fits."""
    fits = [m for m in candidates if m <= padded]
    return max(fits) if fits else padded


def median_ms(values: Sequence[float]) -> Optional[float]:
    return 1e3 * median(values) if values else None
