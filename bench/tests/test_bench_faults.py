"""Each cell's check sees a broken timed path: the harness's look for a
chip is skipped and the rest of a run is driven at a small size on the
CPU, with one fault planted in the program underneath, and ``correct``
has to come out false (and true with no fault)."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.lib import harness  # noqa: E402

SMALL = {
    "search.cardio": {"pop": 4, "generations": 2, "train_steps": 12,
                      "sample": 32},
    "robust.cardio": {"samples": 8},
    "search.whitewine": {"pop": 4, "generations": 2, "train_steps": 12,
                         "sample": 32},
    "robust.whitewine": {"samples": 8},
}


def _result(workload: str, seconds: float = 1.0):
    import jax
    from repro.core import search
    jax.clear_caches()
    search._train_and_score_jit.cache_clear()
    run = harness.build_run(ROOT, workload, 2 ** 31 + 77, seconds, False,
                            time.perf_counter(), require_tpu=False,
                            traffic_override=SMALL[workload])
    return harness.execute(run)


@pytest.fixture(autouse=True)
def _no_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda root: None)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    assert _result(workload)["correct"] is True


def test_search_state_unchanged(monkeypatch):
    from repro.optim import adamw
    monkeypatch.setattr(adamw, "update", lambda g, st, p, **kw: (p, st))
    assert _result("search.cardio")["correct"] is False


def test_search_half_batch(monkeypatch):
    from repro.core import search
    orig = search._train_from_quantized

    def half(xq_tr, xq_te, y_tr, y_te, *a, **kw):
        m = xq_tr.shape[0] // 2
        return orig(xq_tr[:m], xq_te, y_tr[:m], y_te, *a, **kw)

    monkeypatch.setattr(search, "_train_from_quantized", half)
    assert _result("search.cardio")["correct"] is False


def test_search_answer_altered(monkeypatch):
    from repro.core import search
    orig = search.accuracy
    monkeypatch.setattr(search, "accuracy",
                        lambda correct, n: orig(np.asarray(correct) + 1, n))
    assert _result("search.cardio")["correct"] is False


def test_robust_answer_altered(monkeypatch):
    from repro.core import deploy
    orig = deploy._mc_instance_accuracies

    def altered(*a, **kw):
        acc = np.array(orig(*a, **kw))
        acc[0] += np.float32(1.0 / len(a[3]))
        return acc

    monkeypatch.setattr(deploy, "_mc_instance_accuracies", altered)
    assert _result("robust.cardio")["correct"] is False


def test_search_fitness_misplaced(monkeypatch):
    # each genome handed the fitness of its neighbour, as a dedup scatter
    # into the wrong slot would; selection may drop the victims, so the
    # check has to see evaluated individuals, not survivors
    from repro.core import search
    orig = search.evaluate_population
    monkeypatch.setattr(search, "evaluate_population",
                        lambda *a, **kw: np.roll(orig(*a, **kw), 1, axis=0))
    assert _result("search.cardio")["correct"] is False
