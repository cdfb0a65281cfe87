"""The work a classify step needs, counted the same whatever implements it.

The step is TCAM compares and LUT adds on the vector unit, not matrix
products, and a v5e has no published vector-unit peak; an operations bound
taken from the MXU peaks would count one implementation's matmuls.  So the
least time of a step is the bytes it has to move over HBM bandwidth, and the
bytes are counted from what the step needs, never from what a kernel does:

* only real REQUEST packets count, never admission or block padding: each
  reads its used features at the configuration's feature width and writes a
  one-byte answer, plus one byte each of MID and VID;
* each packet counts the one version it addresses, never all V;
* a dispatch reads each table it addresses at least once, and only the
  installed trees, layers and entries count, at the narrowest width the
  profile allows: a TCAM entry is its status-code value and mask
  (``max_layers`` bits each), a feature id, two range bounds at the feature
  width and its set bit; a leaf is its code and a one-byte label; an SVM
  product is a 32-bit fixed-point word;
* a dispatch never has to read more of a table than its packets touch: a
  packet touches one entry per layer along its tree's shortest root-to-leaf
  path, and one product per feature and hyperplane.

Deleting waste or changing a layout can therefore only raise a share built
on this count toward 100%, never past it.
"""
from __future__ import annotations

import math

_HEADER_BYTES = 2      # MID and VID
_RESULT_BYTES = 1      # <= 256 classes
_LUT_BYTES = 4


def _bytes(bits: int) -> int:
    return max(1, math.ceil(bits / 8))


def entry_bytes(profile: dict) -> int:
    """One dt_layer TCAM entry at the narrowest width the profile allows."""
    code = _bytes(profile["max_layers"])
    fid = _bytes(max(1, (profile["max_features"] - 1).bit_length()))
    bound = _bytes(profile["feature_width"])
    return 2 * code + fid + 2 * bound + 1


def leaf_bytes(profile: dict) -> int:
    return _bytes(profile["max_layers"]) + _RESULT_BYTES


def model_cost(fp: dict, n_features: int, profile: dict) -> dict:
    """Per-model byte counts from its fingerprint (``reference.fingerprint``).

    Returns ``table`` (the whole installed table), ``touch`` (what one
    packet touches at least) and ``packet`` (a packet's own bytes)."""
    packet = n_features * _bytes(profile["feature_width"]) + _HEADER_BYTES \
        + _RESULT_BYTES
    if fp["kind"] == "svm":
        per_packet = fp["hyperplanes"] * fp["features"] * _LUT_BYTES
        table = per_packet * (1 << profile["feature_width"])
        return {"table": table, "touch": per_packet, "packet": packet}
    e, leaf = entry_bytes(profile), leaf_bytes(profile)
    internal = [n - lv for n, lv in zip(fp["nodes"], fp["leaves"])]
    # two TCAM entries per internal node (its test and the catch-all)
    table = sum(2 * i * e + lv * leaf for i, lv in zip(internal, fp["leaves"]))
    touch = sum(d * e + leaf for d in fp["min_leaf_depth"])
    return {"table": table, "touch": touch, "packet": packet}


def dispatch_bytes(groups: dict) -> int:
    """Bytes one dispatch needs: ``groups`` maps each addressed model to
    ``(packets, cost)`` with ``cost`` from ``model_cost``."""
    total = 0
    for n, cost in groups.values():
        total += n * cost["packet"] + min(cost["table"], n * cost["touch"])
    return total
