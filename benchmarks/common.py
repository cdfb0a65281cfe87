"""Shared workload definitions (paper Table 2) + fitting helpers.

Datasets are the synthetic stand-ins from repro.data (offline container —
same shapes as paper Table 9; accuracies are proxies, system-level numbers
are faithful).  Feature budgets per system come from paper Tables 3/4.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import time

import jax
import numpy as np

from repro.core.mlmodels import (
    DecisionTree,
    LinearSVM,
    Quantizer,
    RandomForest,
)
from repro.data import load_dataset

# (workload id, dataset, model kind) — paper Table 2.
WORKLOADS = [
    ("1", "nsl-kdd", "dt"),
    ("2", "nsl-kdd", "svm"),
    ("3", "unsw-iot", "rf"),
    ("4", "cicids-17", "dt"),
    ("5", "unsw-nb15", "dt"),
    ("6", "iscxvpn16", "rf"),
    ("7", "cicids-17", "svm"),
    ("8", "vcaml", "rf"),
    ("9", "iris", "svm"),
    ("10", "digits", "rf"),
    ("11", "mnist", "dt"),
    ("12", "satdap", "dt"),
]

# Per-system feature budgets for tree workloads (paper Tables 3/4).
FEATURE_BUDGET = {"switchtree": 16, "leo": 10, "dinc": 32, "acorn": 46}

# Sample-count scales (1 CPU core; shapes preserved).
SCALE = {
    "nsl-kdd": 0.04, "unsw-iot": 0.008, "cicids-17": 0.05, "unsw-nb15": 0.03,
    "iscxvpn16": 1.0, "vcaml": 0.5, "iris": 1.0, "digits": 1.0,
    "mnist": 0.15, "satdap": 1.0,
}


CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


def use_compile_cache() -> None:
    """Keep JAX's persistent compilation cache across runs of an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the path is part of the cache key, so it never
    comes from a temp name, a pid or the time.  Call before the first
    compile; library imports and tests never call it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))


@dataclasses.dataclass
class Fitted:
    model: object
    Xtr: np.ndarray
    ytr: np.ndarray
    Xte: np.ndarray
    yte: np.ndarray
    cols: np.ndarray
    fit_s: float


def topk_features(Xq, y, k: int) -> np.ndarray:
    """Importance-based selection (fast stand-in for the paper's RFE —
    identical intent: pick the k most informative columns)."""
    if Xq.shape[1] <= k:
        return np.arange(Xq.shape[1])
    probe = DecisionTree(max_depth=8, max_leaf_nodes=128, random_state=0).fit(Xq, y)
    imp = probe.feature_importances_()
    order = np.argsort(-imp, kind="stable")
    return np.sort(order[:k])


def fit_workload(dataset: str, kind: str, n_features: int, *,
                 max_leaf_nodes: int = 128, n_estimators: int = 3,
                 seed: int = 0) -> Fitted:
    Xtr, ytr, Xte, yte = load_dataset(dataset, scale=SCALE[dataset],
                                      max_train=6000, max_test=2000)
    q = Quantizer(8).fit(Xtr)
    Xtrq, Xteq = q.transform(Xtr), q.transform(Xte)
    cols = topk_features(Xtrq, ytr, n_features)
    Xtrq, Xteq = Xtrq[:, cols], Xteq[:, cols]
    t0 = time.perf_counter()
    if kind == "dt":
        model = DecisionTree(max_depth=12, max_leaf_nodes=max_leaf_nodes,
                             random_state=seed).fit(Xtrq, ytr)
    elif kind == "rf":
        model = RandomForest(n_estimators=n_estimators, max_depth=8,
                             max_leaf_nodes=max_leaf_nodes // 2,
                             random_state=seed).fit(Xtrq, ytr)
    else:
        model = LinearSVM(epochs=250, random_state=seed).fit(Xtrq, ytr)
    return Fitted(model, Xtrq, ytr, Xteq, yte, cols, time.perf_counter() - t0)
