"""The plain reference that decides ``correct``, and its control.

It imports nothing of the program and reads none of its tables: it takes the
fitted model's own parameters (a tree's node arrays, an SVM's float weights)
and computes what the configuration states a switch answers:

* a tree sends a packet left where ``x[feature] <= threshold`` and answers
  its leaf's label; a forest answers the weighted majority of its trees,
  ties to the smallest class;
* an SVM keeps each hyperplane's product ``w[h, f] * center(x_f)`` as a
  fixed-point integer with ``frac_bits`` fractional bits (round half to
  even), adds them and the rounded bias exactly, keeps the sign bit, and
  answers the one-vs-one (or one-vs-rest) vote over the sign bits, ties to
  the smallest class.

``feature_bits`` below the configuration's width is the control: the same
reference with every feature carried at that narrower precision (int4 for
the 8-bit features the configuration states).  It has to come out as not
correct.
"""
from __future__ import annotations

import numpy as np


def describe(model) -> dict:
    """A plain description of a fitted model: only numpy arrays and ints."""
    if hasattr(model, "W_"):
        return {"kind": "svm", "W": np.asarray(model.W_, np.float64),
                "b": np.asarray(model.b_, np.float64),
                "pairs": [tuple(p) for p in model.pairs_],
                "n_classes": int(model.n_classes_),
                "multi_class": model.multi_class,
                "levels": int(model.levels)}
    trees = [model] if hasattr(model, "tree_") else list(model.trees_)
    weights = getattr(model, "tree_weights", None)
    return {
        "kind": "rf" if hasattr(model, "trees_") else "dt",
        "trees": [{k: np.asarray(getattr(t.tree_, k)) for k in
                   ("feature", "threshold", "left", "right", "label", "depth")}
                  for t in trees],
        "weights": (np.ones(len(trees)) if weights is None
                    else np.asarray(weights, np.float64)),
        "n_classes": int(model.n_classes_),
    }


def fingerprint(desc: dict) -> dict:
    """Shape of what gets installed: printed on every run."""
    if desc["kind"] == "svm":
        return {"kind": "svm", "hyperplanes": int(desc["W"].shape[0]),
                "features": int(desc["W"].shape[1]),
                "classes": desc["n_classes"]}
    trees = desc["trees"]
    return {"kind": desc["kind"], "trees": len(trees),
            "nodes": [int(t["feature"].size) for t in trees],
            "leaves": [int((t["feature"] < 0).sum()) for t in trees],
            "depth": max(int(t["depth"].max()) for t in trees),
            "min_leaf_depth": [int(t["depth"][t["feature"] < 0].min())
                               for t in trees],
            "classes": desc["n_classes"]}


def _narrow(X, feature_bits, width_bits):
    """Carry ``width_bits``-wide features at ``feature_bits`` of precision
    (each value replaced by the middle of its coarser bin)."""
    X = np.asarray(X, np.int64)
    if feature_bits >= width_bits:
        return X
    step = 1 << (width_bits - feature_bits)
    return (X // step) * step + step // 2


def _tree_leaf_labels(tree, X):
    node = np.zeros(X.shape[0], np.int64)
    rows = np.arange(X.shape[0])
    feat, thr = tree["feature"], tree["threshold"]
    left, right = tree["left"], tree["right"]
    while True:
        f = feat[node]
        inner = f >= 0
        if not inner.any():
            return tree["label"][node].astype(np.int64)
        go_left = X[rows, np.where(inner, f, 0)] <= thr[node]
        nxt = np.where(go_left, left[node], right[node])
        node = np.where(inner, nxt, node)


def _vote(labels, weights, n_classes):
    scores = np.zeros((labels.shape[0], n_classes))
    for t in range(labels.shape[1]):
        scores[np.arange(labels.shape[0]), labels[:, t]] += weights[t]
    return np.argmax(scores, axis=1).astype(np.int64)


def _svm_signs(desc, X, frac_bits):
    levels = desc["levels"]
    scale = float(1 << frac_bits)
    centers = (np.arange(levels) + 0.5) / levels
    W = desc["W"]
    sums = np.round(desc["b"] * scale).astype(np.int64)[None, :].repeat(
        X.shape[0], axis=0)
    for f in range(W.shape[1]):
        lut = np.round(W[:, f][:, None] * centers[None, :] * scale
                       ).astype(np.int64)               # [H, levels]
        sums += lut[:, X[:, f]].T
    return (sums >= 0).astype(np.int64)


def _svm_vote(desc, signs):
    C = desc["n_classes"]
    if desc["multi_class"] == "ovr" and C == 2:
        return signs[:, 0]
    scores = np.zeros((signs.shape[0], C))
    for h, (i, j) in enumerate(desc["pairs"]):
        pos = signs[:, h] == 1
        scores[pos, i] += 1
        if j >= 0:
            scores[~pos, j] += 1
    return np.argmax(scores, axis=1).astype(np.int64)


def predict(desc: dict, X, *, width_bits: int, frac_bits: int,
            feature_bits: int | None = None) -> np.ndarray:
    """Labels the configuration states for rows ``X`` (quantized ints).

    ``feature_bits`` narrower than ``width_bits`` computes the control."""
    X = _narrow(X, width_bits if feature_bits is None else feature_bits,
                width_bits)
    if desc["kind"] == "svm":
        return _svm_vote(desc, _svm_signs(desc, X, frac_bits))
    labels = np.stack([_tree_leaf_labels(t, X) for t in desc["trees"]],
                      axis=1)
    if desc["kind"] == "dt":
        return labels[:, 0]
    return _vote(labels, desc["weights"], desc["n_classes"])
