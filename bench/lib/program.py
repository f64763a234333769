"""Arithmetic shared by the per-layer metric readers that read the
program's own spans (``repro.obs``): from its ring of records over the
whole measured window, or from its ``repro.``-prefixed host events in the
device trace.

A ring record is ``(id, parent_id, name, t0_ns, t1_ns, args)`` on the
clock of ``time.perf_counter``, the clock the harness times its window
with. A program that keeps no such ring, or whose ring no longer reaches
back to the window's start, gives None, and so does a window with no
span of the step asked for.
"""
from __future__ import annotations

import bisect
from statistics import median
from typing import Dict, Iterable, List, Optional, Sequence

PREFIX = "repro."
# host events of one dispatch to the device
DISPATCH_MARKS = ("PjitFunction", "DevicePut")


def ring_records():
    """The program's ring as (records, capacity); None where the program
    keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.records(), obs.CAPACITY


def window_records(records: Sequence, capacity: int, t0_s: float,
                   window_s: float) -> Optional[List]:
    """The records that lie wholly inside [t0_s, t0_s + window_s]; None if
    the ring is full and its oldest record ended after t0_s (records made
    in the window may have been dropped)."""
    lo, hi = int(t0_s * 1e9), int((t0_s + window_s) * 1e9)
    if len(records) >= capacity and records and records[0][4] > lo:
        return None
    return [r for r in records if lo <= r[3] and r[4] <= hi]


def per_root_ms(records: Iterable, root: str,
                names: Sequence[str]) -> Optional[float]:
    """Median over the ``root`` spans of the summed time, in ms, of their
    descendants named in ``names`` (a root without any counts 0)."""
    records = list(records)
    by_id = {r[0]: r for r in records}
    total: Dict[int, int] = {r[0]: 0 for r in records if r[2] == root}
    if not total:
        return None
    for r in records:
        if r[2] not in names:
            continue
        p = r[1]
        while p is not None and p in by_id and by_id[p][2] != root:
            p = by_id[p][1]
        if p in total:
            total[p] += r[4] - r[3]
    return median(total.values()) / 1e6


def window_ms(obs, root: str, names: Sequence[str]) -> Optional[float]:
    """``per_root_ms`` over the ring records of the run's window."""
    ring = ring_records()
    run = obs.run
    if ring is None or run.window_t0 is None or not run.window_s:
        return None
    recs = window_records(ring[0], ring[1], run.window_t0, run.window_s)
    return None if recs is None else per_root_ms(recs, root, names)


def dispatches_within(host: Sequence[Dict], root: str) -> Optional[float]:
    """Median, over the trace's ``repro.<root>`` host events, of the
    dispatch events (``PjitFunction*``, ``DevicePut*``) wholly inside
    each."""
    roots = [h for h in host if h["name"] == PREFIX + root]
    if not roots:
        return None
    marks = sorted((h["start"], h["start"] + h["dur"]) for h in host
                   if h["name"].startswith(DISPATCH_MARKS))
    starts = [s for s, _ in marks]
    counts = []
    for r in roots:
        s0, s1 = r["start"], r["start"] + r["dur"]
        i, j = bisect.bisect_left(starts, s0), bisect.bisect_right(starts, s1)
        counts.append(sum(1 for _, e in marks[i:j] if e <= s1))
    return float(median(counts))


def trace_dispatches(obs, root: str) -> Optional[float]:
    """``dispatches_within`` over the run's extracted trace."""
    ex = obs.run.tracer.extracted
    return dispatches_within(ex["host"], root) if ex else None
