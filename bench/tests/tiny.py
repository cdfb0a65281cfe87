"""Tiny cells for the CPU tests: the harness's real configurations and
mixes, cut to a size the CPU runs in seconds."""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_PROFILE = {"max_features": 8, "feature_width": 8, "max_trees": 2,
                "max_layers": 6, "max_entries_per_layer": 64,
                "max_leaves": 64, "max_classes": 32, "max_hyperplanes": 2,
                "levels": 256}
TINY_HYPER = {"dt": {"max_depth": 5, "max_leaf_nodes": 16},
              "rf": {"n_estimators": 2, "max_depth": 4, "max_leaf_nodes": 8},
              "svm": {"epochs": 20}}


def tiny_cell(config: str, traffic: str, **traffic_over) -> dict:
    """The cell ``config.traffic`` at a CPU size: a small profile, small
    models, low load."""
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    tr = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    cfg["profile"] = dict(TINY_PROFILE,
                          max_versions=cfg["profile"]["max_versions"])
    cfg["hyper"] = TINY_HYPER
    cfg["feature_budget"] = 8
    tr = dict(tr)
    if tr["loop"] == "open":
        tr["rate_rps"] = 200
    else:
        tr["clients"] = 4
        tr["cycle"] = 64
        tr["packets_per_request"] = [8, 32]
        tr["policy"] = {"max_batch": 64, "max_wait_us": 2000}
    tr.update(traffic_over)
    return {"name": f"{config}.{traffic}", "chips": 1, "config": cfg,
            "traffic": tr,
            "end_to_end": ["setup_s", "p50_ms", "pkts_per_s",
                           "swap_ms"],
            "per_layer": ["queue_wait_ms", "service_ms", "batch_pkts.sat",
                          "install_ms"]}
