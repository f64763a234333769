"""The benchmark's own data: seeded synthetic tabular sets with the
dimensions of a UCI sensor-classification set (features, classes,
samples, [0, 1] per-feature normalization, 70/30 stratified split).

Each class is a two-component Gaussian mixture whose means and scales are
drawn from the set's name and the seed; ``difficulty`` scales the
spreads. The arithmetic is a copy of the program's synthetic-set
generator, kept here so that a change to the program cannot change the
benchmark's inputs.
"""
from __future__ import annotations

import zlib
from typing import Dict

import numpy as np


def make_dataset(name: str, features: int, classes: int, samples: int,
                 difficulty: float, seed: int) -> Dict[str, np.ndarray]:
    """dict(x_train, y_train, x_test, y_test); x float32 in [0, 1]."""
    rng = np.random.default_rng(zlib.crc32(name.encode()) + seed)
    n_per = samples // classes
    xs, ys = [], []
    for c in range(classes):
        for comp in range(2):
            mean = rng.uniform(0.2, 0.8, size=features)
            sigma = rng.uniform(0.5, 1.5, size=features) * difficulty
            cnt = n_per // 2 + (n_per % 2 if comp == 0 else 0)
            xs.append(rng.normal(mean, sigma, size=(cnt, features)))
            ys.append(np.full(cnt, c, np.int32))
    x = np.concatenate(xs).astype(np.float32)
    y = np.concatenate(ys)
    x = (x - x.min(0)) / np.maximum(x.max(0) - x.min(0), 1e-9)
    return stratified_split(x, y, test_frac=0.30, seed=seed)


def stratified_split(x: np.ndarray, y: np.ndarray, test_frac: float,
                     seed: int) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed + 17)
    tr_idx, te_idx = [], []
    for c in np.unique(y):
        idx = np.where(y == c)[0]
        rng.shuffle(idx)
        k = max(1, int(round(len(idx) * test_frac)))
        te_idx.append(idx[:k])
        tr_idx.append(idx[k:])
    tr = np.concatenate(tr_idx)
    te = np.concatenate(te_idx)
    rng.shuffle(tr)
    return {"x_train": x[tr], "y_train": y[tr],
            "x_test": x[te], "y_test": y[te]}


def for_config(config: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The data set a configuration file describes, from ``seed``."""
    d = config["data"]
    return make_dataset(d["name"], config["channels"], config["classes"],
                        d["samples"], d["difficulty"], seed)
