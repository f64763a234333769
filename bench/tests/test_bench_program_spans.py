"""The readers of the program's own spans (bench/lib/program.py and the
metrics that use it), on a synthetic ring and a synthetic extracted
trace with answers worked out by hand; and once on the program's real
ring."""
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import harness, program  # noqa: E402

MS = 1_000_000                    # ns
T0 = 100.0                        # window start, s on perf_counter
W0 = int(T0 * 1e9)


def rec(i, parent, name, t0_ms, t1_ms, **args):
    """A ring record at t0_ms..t1_ms after the window's start."""
    return (i, parent, name, W0 + int(t0_ms * MS), W0 + int(t1_ms * MS),
            args)


def search_ring():
    """Two generations in the window and one that began before it.

    gen 10 (0..60 ms): variation 2, survival 3, areas 4 (under evaluate),
    init 5, fetch 30; gen 20 (100..170): variation 4, survival 5, areas 6,
    init 7, fetch 40; gen 30 (100 ms before the window) is left out, and
    so is gen 40, which ends after it."""
    return [
        rec(31, 30, "nsga2.variation", -100, -90),
        rec(30, None, "nsga2.generation", -100, -10),
        rec(11, 10, "nsga2.variation", 0, 2),
        rec(13, 12, "search.init", 2, 7),
        rec(14, 12, "search.fetch", 8, 38),
        rec(15, 12, "search.areas", 38, 42),
        rec(16, 13, "python.gc", 3, 4),
        rec(12, 10, "search.evaluate", 2, 55),
        rec(17, 10, "nsga2.survival", 55, 58),
        rec(10, 1, "nsga2.generation", 0, 60),
        rec(21, 20, "nsga2.variation", 100, 104),
        rec(23, 22, "search.init", 104, 111),
        rec(24, 22, "search.fetch", 111, 151),
        rec(25, 22, "search.areas", 151, 157),
        rec(22, 20, "search.evaluate", 104, 160),
        rec(27, 20, "nsga2.survival", 160, 165),
        rec(20, 1, "nsga2.generation", 100, 170),
        rec(41, 40, "nsga2.variation", 990, 995),
        rec(40, 1, "nsga2.generation", 990, 1010),
    ]


def robust_ring():
    """Three calls: operands 10, 20, 60 ms; report 1, 2 and none."""
    out = []
    for k, (ops, rep) in enumerate(((10, 1), (20, 2), (60, 0))):
        base, i = 200 * k, 100 * (k + 1)
        out.append(rec(i + 1, i, "deploy.mc_operands", base, base + ops))
        out.append(rec(i + 2, i, "deploy.mc_launch", base + ops,
                       base + ops + 1))
        out.append(rec(i + 3, i, "deploy.rescore", base + ops + 1,
                       base + 150))
        if rep:
            out.append(rec(i + 4, i, "deploy.report", base + 150,
                           base + 150 + rep))
        out.append(rec(i, None, "deploy.evaluate_robustness", base,
                       base + 160, designs=8, samples=1024))
    return out


def fake_obs(host=None, window_s=1.0):
    tracer = SimpleNamespace(extracted=None if host is None
                             else {"devices": [], "host": host})
    return SimpleNamespace(run=SimpleNamespace(window_t0=T0,
                                               window_s=window_s,
                                               tracer=tracer))


def reader(name):
    return harness.load_module(harness.metric_file(ROOT, name), "metric")


@pytest.fixture
def ring(monkeypatch):
    """Hand the readers a synthetic ring of the given capacity."""
    def use(records, capacity=65536):
        monkeypatch.setattr(program, "ring_records",
                            lambda: (records, capacity))
    return use


# ------------------------------------------------------------- the ring
def test_window_keeps_only_records_wholly_inside():
    recs = program.window_records(search_ring(), 65536, T0, 1.0)
    ids = {r[0] for r in recs}
    assert {10, 20, 11, 27} <= ids
    assert not ids & {30, 31, 40}            # before, and across the end
    assert 41 in ids                         # ends at 995 ms, inside


def test_window_is_none_once_the_ring_wrapped_past_its_start():
    recs = search_ring()[2:]                  # oldest kept ends at 2 ms
    assert program.window_records(recs, len(recs), T0, 1.0) is None
    # full, but the oldest record kept ended before the window began
    full = search_ring()
    assert program.window_records(full, len(full), T0, 1.0) is not None
    # not full: nothing was dropped
    assert program.window_records(recs, len(recs) + 1, T0, 1.0) is not None


def test_per_root_sums_descendants_and_takes_the_median():
    recs = program.window_records(search_ring(), 65536, T0, 1.0)
    # gens 10 and 20 (40 crosses the window's end; its variation, inside,
    # has no root to count towards)
    assert program.per_root_ms(recs, "nsga2.generation",
                               ("nsga2.variation", "nsga2.survival")) \
        == pytest.approx(7.0)                 # median of 2 + 3, 4 + 5
    # areas 4 and 6 ms, each under the generation's search.evaluate
    assert program.per_root_ms(recs, "nsga2.generation",
                               ("search.areas",)) == pytest.approx(5.0)
    assert program.per_root_ms(recs, "nsga2.generation", ("nope",)) == 0.0
    assert program.per_root_ms(recs, "no.root", ("search.areas",)) is None
    assert program.per_root_ms([], "nsga2.generation", ("x",)) is None


def test_search_readers_on_a_synthetic_ring(ring):
    ring(search_ring())
    obs = fake_obs()
    assert reader("nsga2_ms.search").read(obs) == pytest.approx(7.0)
    assert reader("areas_ms.search").read(obs) == pytest.approx(5.0)
    assert reader("init_ms.search").read(obs) == pytest.approx(6.0)
    assert reader("fetch_ms.search").read(obs) == pytest.approx(35.0)
    # the window ends before the second generation: one generation left
    short = fake_obs(window_s=0.1)
    assert reader("fetch_ms.search").read(short) == pytest.approx(30.0)


def test_robust_readers_on_a_synthetic_ring(ring):
    ring(robust_ring())
    obs = fake_obs()
    assert reader("operands_ms.robust").read(obs) == pytest.approx(20.0)
    assert reader("report_ms.robust").read(obs) == pytest.approx(1.0)


def test_ring_readers_give_none_without_spans_or_ring(ring, monkeypatch):
    names = ("nsga2_ms.search", "areas_ms.search", "init_ms.search",
             "fetch_ms.search", "operands_ms.robust", "report_ms.robust")
    ring([])                                           # no spans at all
    assert all(reader(n).read(fake_obs()) is None for n in names)
    ring(robust_ring())                                # another cell's
    assert reader("nsga2_ms.search").read(fake_obs()) is None
    recs = search_ring()[2:]
    ring(recs, capacity=len(recs))                     # wrapped
    assert reader("nsga2_ms.search").read(fake_obs()) is None
    # a program without repro.obs
    monkeypatch.setattr(program, "ring_records", lambda: None)
    assert all(reader(n).read(fake_obs()) is None for n in names)
    # a run whose window never began
    ring(search_ring())
    unrun = SimpleNamespace(run=SimpleNamespace(window_t0=None,
                                                window_s=None))
    assert reader("nsga2_ms.search").read(unrun) is None


def test_ring_readers_on_the_programs_own_ring():
    from repro import obs as program_obs
    obs = SimpleNamespace(run=SimpleNamespace(window_t0=time.perf_counter()))
    for _ in range(3):
        with program_obs.span("nsga2.generation"):
            with program_obs.span("nsga2.variation"):
                time.sleep(0.002)
            with program_obs.span("search.evaluate"):
                with program_obs.span("search.areas"):
                    time.sleep(0.001)
            with program_obs.span("nsga2.survival"):
                time.sleep(0.002)
    obs.run.window_s = time.perf_counter() - obs.run.window_t0
    assert 4.0 <= reader("nsga2_ms.search").read(obs) < 50.0
    assert 1.0 <= reader("areas_ms.search").read(obs) < 25.0
    assert reader("init_ms.search").read(obs) == 0.0


# ------------------------------------------------------------ the trace
def ev(name, start, dur):
    return {"name": name, "start": float(start), "dur": float(dur)}


HOST = [
    ev("bench.run_search", 0, 10_000),
    ev("repro.nsga2.generation", 100, 1_000),
    ev("PjitFunction(tile)", 150, 10),
    ev("DevicePut", 200, 5),
    ev("DevicePutWithSharding", 300, 5),
    ev("PjitFunction(_train_and_score)", 1_095, 10),   # ends after: out
    ev("np.asarray(jax.Array)", 400, 50),              # not a dispatch
    ev("repro.nsga2.generation", 2_000, 1_000),
    ev("PjitFunction(tile)", 2_100, 10),
    ev("repro.nsga2.generation", 4_000, 1_000),
    ev("PjitFunction(tile)", 4_100, 10),
    ev("PjitFunction(broadcast_in_dim)", 4_200, 10),
    ev("PjitFunction(convert_element_type)", 4_300, 10),
    ev("PjitFunction(tile)", 6_000, 10),               # between: out
    ev("repro.deploy.evaluate_robustness", 7_000, 2_000),
    ev("PjitFunction(bitwise_and)", 7_100, 10),
    ev("PjitFunction(_take)", 7_200, 10),
]


def test_dispatches_counted_inside_each_root():
    # generations hold 3, 1 and 3 dispatches
    assert program.dispatches_within(HOST, "nsga2.generation") == 3.0
    assert program.dispatches_within(HOST, "deploy.evaluate_robustness") \
        == 2.0
    assert program.dispatches_within(HOST, "search.run") is None


def test_dispatch_readers_on_a_synthetic_trace():
    obs = fake_obs(host=HOST)
    assert reader("dispatches_per_gen.search").read(obs) == 3.0
    assert reader("dispatches_per_call.robust").read(obs) == 2.0
    # a trace without the program's spans (the program before them)
    bare = fake_obs(host=[h for h in HOST
                          if not h["name"].startswith("repro.")])
    assert reader("dispatches_per_gen.search").read(bare) is None
    assert reader("dispatches_per_call.robust").read(bare) is None
    assert reader("dispatches_per_gen.search").read(fake_obs()) is None
