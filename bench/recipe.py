"""What a cell installs: the datasets and the fit recipe, frozen here.

Copied so that a change to the program cannot move the yardstick:

* ``make_classification``, ``DatasetSpec`` and the rows of ``DATASETS`` that
  the cells use come from ``src/repro/data/synth.py`` (seeded stand-ins with
  the shapes of the paper's Table 9);
* ``SCALE``, the training-row caps, ``topk_features`` and the
  hyperparameters come from ``benchmarks/common.py`` (``fit_workload``);
* ``quantize`` is ``Quantizer(8)`` of ``core/mlmodels/preprocess.py``.

The learners themselves (``DecisionTree``, ``RandomForest``, ``LinearSVM``)
are the program's: a user hands ACORN a trained model, and the program only
accepts its own model classes.  What they produce is printed as a
fingerprint on every run, so a change in what gets installed shows.

A model is fitted from the run's seed: it trains on a bootstrap of its
training rows drawn from the seed, so the output check varies the
deployment as well as the traffic.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def make_classification(n_samples, n_features, n_classes, *, n_informative=None,
                        n_redundant=None, class_sep=1.6, imbalance=0.0,
                        label_noise=0.02, seed=0):
    """Gaussian-cluster classification data (copy of ``repro.data.synth``)."""
    rng = np.random.default_rng(seed)
    if n_informative is None:
        n_informative = max(2, min(n_features, int(np.ceil(
            np.log2(max(n_classes, 2)) + 3))))
    n_informative = min(n_informative, n_features)
    if n_redundant is None:
        n_redundant = min(n_features - n_informative, n_informative)
    pri = (1.0 - imbalance) ** np.arange(n_classes)
    pri = pri / pri.sum()
    y = rng.choice(n_classes, size=n_samples, p=pri)
    n_clusters = 2
    means = rng.uniform(-1, 1, size=(n_classes, n_clusters, n_informative))
    means *= class_sep / np.maximum(np.linalg.norm(
        means, axis=-1, keepdims=True), 1e-9) * np.sqrt(n_informative)
    cluster = rng.integers(0, n_clusters, size=n_samples)
    Xi = means[y, cluster] + rng.normal(size=(n_samples, n_informative))
    blocks = [Xi]
    if n_redundant > 0:
        A = rng.normal(size=(n_informative, n_redundant))
        blocks.append(Xi @ A + 0.1 * rng.normal(size=(n_samples, n_redundant)))
    n_noise = n_features - n_informative - n_redundant
    if n_noise > 0:
        blocks.append(rng.normal(size=(n_samples, n_noise)))
    X = np.concatenate(blocks, axis=1)
    X = X[:, rng.permutation(n_features)]
    flip = rng.random(n_samples) < label_noise
    y[flip] = rng.choice(n_classes, size=int(flip.sum()), p=pri)
    return X, y.astype(np.int64)


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    n_train: int
    n_test: int
    n_features: int
    n_classes: int
    imbalance: float = 0.0
    class_sep: float = 1.6
    seed: int = 0


# Paper Table 9 shapes, verbatim (the rows the cells use).
DATASETS = {s.name: s for s in [
    DatasetSpec("nsl-kdd", 125_948, 22_544, 119, 2, imbalance=0.15, seed=101),
    DatasetSpec("unsw-iot", 626_463, 143_141, 30, 25, imbalance=0.12,
                class_sep=1.9, seed=102),
    DatasetSpec("cicids-17", 102_996, 34_333, 78, 2, imbalance=0.3, seed=103),
    DatasetSpec("unsw-nb15", 175_341, 75_641, 166, 2, imbalance=0.2, seed=104),
    DatasetSpec("iscxvpn16", 2_357, 590, 23, 2, seed=105),
]}


def load_dataset(name, *, scale, max_train, max_test):
    """(X_train, y_train, X_test, y_test) at ``scale`` of Table 9's rows,
    capped; feature and class counts are never scaled."""
    spec = DATASETS[name]
    n_tr = min(int(spec.n_train * scale), max_train)
    n_te = min(int(spec.n_test * scale), max_test)
    n_tr = max(n_tr, 8 * spec.n_classes)
    n_te = max(n_te, 2 * spec.n_classes)
    X, y = make_classification(
        n_tr + n_te, spec.n_features, spec.n_classes,
        imbalance=spec.imbalance, class_sep=spec.class_sep, seed=spec.seed)
    return X[:n_tr], y[:n_tr], X[n_tr:], y[n_tr:]


def quantize(Xtr, Xte, bits):
    """Min-max scale on the training rows, then ``bits``-wide integers."""
    lo, hi = Xtr.min(axis=0), Xtr.max(axis=0)
    hi = np.where(hi - lo == 0, lo + 1.0, hi)
    levels = 1 << bits

    def q(X):
        unit = np.clip((X - lo) / (hi - lo), 0.0, np.nextafter(1.0, 0.0))
        return np.clip(np.floor(unit * levels).astype(np.int64), 0, levels - 1)

    return q(Xtr), q(Xte)


def topk_features(Xq, y, k):
    """The k most important columns of a depth-8 probe tree."""
    from repro.core.mlmodels import DecisionTree

    if Xq.shape[1] <= k:
        return np.arange(Xq.shape[1])
    probe = DecisionTree(max_depth=8, max_leaf_nodes=128,
                         random_state=0).fit(Xq, y)
    order = np.argsort(-probe.feature_importances_(), kind="stable")
    return np.sort(order[:k])


@dataclasses.dataclass
class Prepared:
    """One dataset quantized and cut to its selected columns."""

    Xtr: np.ndarray
    ytr: np.ndarray
    Xte: np.ndarray
    cols: np.ndarray


def prepare(dataset, recipe):
    """Load, quantize and select features for one dataset, as ``recipe``
    (the configuration's ``recipe`` group) says."""
    Xtr, ytr, Xte, _ = load_dataset(
        dataset, scale=recipe["dataset_scale"][dataset],
        max_train=recipe["train_rows_max"], max_test=recipe["test_rows_max"])
    Xtrq, Xteq = quantize(Xtr, Xte, recipe["feature_bits"])
    cols = topk_features(Xtrq, ytr, recipe["feature_budget"])
    return Prepared(Xtrq[:, cols], ytr, Xteq[:, cols].astype(np.int32), cols)


def fit(kind, prep, hyper, seed):
    """Fit one model of ``kind`` on a seed-drawn bootstrap of the rows."""
    from repro.core.mlmodels import DecisionTree, LinearSVM, RandomForest

    rng = np.random.default_rng(seed)
    idx = rng.integers(0, prep.Xtr.shape[0], prep.Xtr.shape[0])
    X, y = prep.Xtr[idx], prep.ytr[idx]
    state = int(rng.integers(0, 2**31))
    if kind == "dt":
        return DecisionTree(max_depth=hyper["max_depth"],
                            max_leaf_nodes=hyper["max_leaf_nodes"],
                            random_state=state).fit(X, y)
    if kind == "rf":
        return RandomForest(n_estimators=hyper["n_estimators"],
                            max_depth=hyper["max_depth"],
                            max_leaf_nodes=hyper["max_leaf_nodes"],
                            random_state=state).fit(X, y)
    if kind == "svm":
        return LinearSVM(epochs=hyper["epochs"], random_state=state).fit(X, y)
    raise ValueError(f"unknown model kind {kind!r}")
