"""Program spans and counters (repro.obs, DESIGN.md §16): nesting and
parents, args, counters, the bounded ring, garbage collections as spans,
and spans whose body raises."""
import gc
import glob
import threading

import jax
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def _named(name):
    return [r for r in obs.records() if r.name == name]


def test_nesting_gives_parents_and_order():
    with obs.span("root") as root_args:
        with obs.span("child.a"):
            with obs.span("leaf"):
                pass
        with obs.span("child.b"):
            pass
    (root,), (a,), (leaf,), (b,) = (_named(n) for n in
                                    ("root", "child.a", "leaf", "child.b"))
    assert root.parent_id is None and root_args == {}
    assert a.parent_id == root.id and b.parent_id == root.id
    assert leaf.parent_id == a.id
    # a record is appended when its span ends: children before parents
    names = [r.name for r in obs.records() if r.name != "python.gc"]
    assert names == ["leaf", "child.a", "child.b", "root"]
    assert root.t0_ns <= a.t0_ns <= leaf.t0_ns <= leaf.t1_ns <= a.t1_ns
    assert a.t1_ns <= b.t0_ns <= b.t1_ns <= root.t1_ns
    with obs.span("after"):
        pass
    assert _named("after")[0].parent_id is None


def test_args_on_entry_and_added_by_the_body():
    with obs.span("eval", individuals=32) as args:
        args["unique"] = 7
    (r,) = _named("eval")
    assert r.args == {"individuals": 32, "unique": 7}


def test_counters_add_and_reset():
    obs.count("x")
    obs.count("x", 4)
    obs.count("y", 2.5)
    assert obs.counters() == {"x": 5, "y": 2.5}
    c = obs.counters()
    c["x"] = 0                                    # a copy
    assert obs.counters()["x"] == 5
    obs.reset()
    assert obs.counters() == {} and obs.records() == []


def test_counters_from_many_threads_lose_nothing():
    def add():
        for _ in range(2000):
            obs.count("n")
    threads = [threading.Thread(target=add) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert obs.counters()["n"] == 16000


def test_threads_keep_their_own_parents():
    got = {}

    def work(i):
        with obs.span(f"root.{i}"):
            with obs.span(f"child.{i}"):
                pass
        got[i] = True
    with obs.span("main"):
        threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    assert len(got) == 4
    for i in range(4):
        (root,), (child,) = _named(f"root.{i}"), _named(f"child.{i}")
        assert root.parent_id is None and child.parent_id == root.id


def test_ring_keeps_the_newest_capacity_records():
    gc.disable()                  # no python.gc records among the count
    try:
        for i in range(obs.CAPACITY + 10):
            with obs.span("s", i=i):
                pass
    finally:
        gc.enable()
    recs = obs.records()
    assert len(recs) == obs.CAPACITY
    assert recs[0].args["i"] == 10 and recs[-1].args["i"] == obs.CAPACITY + 9
    ids = [r.id for r in recs]
    assert ids == sorted(ids)


def test_garbage_collection_is_a_span():
    class Cycle:
        pass
    with obs.span("holder"):
        for _ in range(5):
            a, b = Cycle(), Cycle()
            a.other, b.other = b, a
            del a, b
        gc.collect()
    (holder,) = _named("holder")
    gcs = [r for r in _named("python.gc") if r.parent_id == holder.id]
    assert gcs, "gc.collect() left no python.gc record"
    assert any(r.args["generation"] == 2 for r in gcs)      # gc.collect()
    assert sum(r.args["collected"] for r in gcs) >= 10
    assert all(holder.t0_ns <= r.t0_ns <= r.t1_ns <= holder.t1_ns
               for r in gcs)


def test_a_body_that_raises_is_recorded_and_marked():
    class Stop(Exception):
        pass
    with pytest.raises(Stop):
        with obs.span("outer"):
            with obs.span("inner", k=1):
                raise Stop
    (outer,), (inner,) = _named("outer"), _named("inner")
    assert inner.args == {"k": 1, "error": "Stop"}
    assert outer.args == {"error": "Stop"}
    assert inner.parent_id == outer.id and inner.t1_ns <= outer.t1_ns
    # the stack unwound: the next span is a root again
    with obs.span("next"):
        pass
    assert _named("next")[0].parent_id is None


def test_spans_reach_the_profiler(tmp_path):
    """Inside a profile each span is a ``repro.``-prefixed host event with
    its entry args as stats."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("traced", pop=4):
            jax.numpy.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU" for line in p.lines
              for e in line.events if e.name == "repro.traced"]
    assert len(events) == 1
    assert dict(events[0].stats)["pop"] == 4
    (r,) = _named("traced")
    # one span, one clock's worth of difference: the ring's duration and
    # the profiler's agree to well under a millisecond
    assert abs((r.t1_ns - r.t0_ns) - events[0].duration_ns) < 1e6
