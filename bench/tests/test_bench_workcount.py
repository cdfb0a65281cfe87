"""The work count, the peaks table and the reference, by hand at a tiny
profile."""
from __future__ import annotations

import numpy as np
import pytest

from bench import reference, workcount
from bench.peaks import PEAKS, peaks

PROFILE = {"max_features": 8, "feature_width": 8, "max_trees": 2,
           "max_layers": 6, "max_entries_per_layer": 64, "max_leaves": 64,
           "max_classes": 8, "max_hyperplanes": 2, "levels": 256,
           "max_versions": 1}

# depth-2 tree: root (f0 <= 100), left leaf 0, right node (f1 <= 7) with
# leaves 1 and 0
TREE = {"feature": np.array([0, -1, 1, -1, -1]),
        "threshold": np.array([100, 0, 7, 0, 0]),
        "left": np.array([1, -1, 3, -1, -1]),
        "right": np.array([2, -1, 4, -1, -1]),
        "label": np.array([0, 0, 1, 1, 0]),
        "depth": np.array([0, 1, 1, 2, 2])}
DT = {"kind": "dt", "trees": [TREE], "weights": np.ones(1), "n_classes": 2}


def test_hand_counts_at_a_tiny_profile():
    fp = reference.fingerprint(DT)
    assert fp["nodes"] == [5] and fp["leaves"] == [3]
    assert fp["min_leaf_depth"] == [1]
    # entry: code value + mask at 6 bits (1 byte each), fid (1), two bounds
    # at 8 bits (1 each), set bit (1)
    assert workcount.entry_bytes(PROFILE) == 2 + 1 + 2 + 1
    assert workcount.leaf_bytes(PROFILE) == 1 + 1
    cost = workcount.model_cost(fp, 2, PROFILE)
    assert cost["table"] == 2 * 2 * 6 + 3 * 2     # 2 internal nodes
    assert cost["touch"] == 1 * 6 + 2
    assert cost["packet"] == 2 + 2 + 1
    # one packet touches less than the table; ten read it whole
    assert workcount.dispatch_bytes({"a": (1, cost)}) == 5 + 8
    assert workcount.dispatch_bytes({"a": (10, cost)}) == 50 + 30
    svm = {"kind": "svm", "hyperplanes": 1, "features": 2, "classes": 2}
    s = workcount.model_cost(svm, 2, PROFILE)
    assert s["touch"] == 2 * 4 and s["table"] == 2 * 4 * 256


def test_count_ignores_versions_and_padding():
    """The count reads real packets of the addressed model only: V, the
    admission bucket and the kernel's block do not enter it."""
    fp = reference.fingerprint(DT)
    base = workcount.model_cost(fp, 2, PROFILE)
    for V in (1, 8):
        cost = workcount.model_cost(fp, 2, dict(PROFILE, max_versions=V))
        assert cost == base
        # 37 real packets cost the same whether admitted into a bucket of 64
        # or a kernel block of 256
        assert workcount.dispatch_bytes({"a": (37, cost)}) \
            == 37 * base["packet"] + base["table"]


def test_reference_tree_by_hand():
    X = np.array([[100, 0], [101, 7], [101, 8], [0, 255]])
    got = reference.predict(DT, X, width_bits=8, frac_bits=12)
    assert got.tolist() == [0, 1, 0, 0]
    # the control carries features at 4 bits (each the middle of its 16-wide
    # bin): 100 and 101 read as 104, 7 and 8 as 8
    low = reference.predict(DT, X, width_bits=8, frac_bits=12,
                            feature_bits=4)
    assert low.tolist() == [0, 0, 0, 0]


def test_reference_svm_fixed_point_by_hand():
    svm = {"kind": "svm", "W": np.array([[1.0, -1.0]]), "b": np.array([0.0]),
           "pairs": [(0, 1)], "n_classes": 2, "multi_class": "ovo",
           "levels": 256}
    X = np.array([[10, 10], [10, 11], [11, 10]])
    # equal products sum to 0: sign bit 1 votes class 0
    got = reference.predict(svm, X, width_bits=8, frac_bits=12)
    assert got.tolist() == [0, 1, 0]


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert all("source" in p for p in PEAKS.values())
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
