"""A cell at a size the CPU holds, for the harness's own tests."""

from __future__ import annotations

import copy
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent


def small_specs(config: str = "svc2048", traffic: str = "ingest_closed"):
    """(bench, cell, config, traffic) of a tiny copy of a real cell."""
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
    tr = json.loads((HERE / "traffic" / f"{traffic}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["capacity"] = 47
    cfg["hosts"] = 39
    cfg["gateway"]["max_queue_values"] = 4096
    if cfg.get("num_slices"):
        cfg["num_slices"] = 8
        cfg["slice_interval_s"] = 1.0
        cfg["prefill"]["values_per_slice"] = 1500
        cfg["check_windows"] = ["2m", "5m"]
        cfg["check_window_keys"] = 16
    tr = copy.deepcopy(tr)
    for s in tr["streams"]:
        s["processes"] = 1
        if "bodies_per_process" in s:
            s["bodies_per_process"] = 64
            s["values_per_post"] = {"low": 8, "high": 128}
        if "senders" in s:
            s["senders"] = 2
        if "values_per_s" in s:
            s["values_per_s"] = 4000
        if "requests_per_s" in s:
            s["requests_per_s"] = 10
            s["sample_answers"] = 20
        for m in s.get("mix", []):
            if m.get("window") == "1h":
                m["window"] = "5m"
        if "probes_per_s" in s:
            s["probes_per_s"] = 4
            s["probe_keys"] = 2
    name = f"{config}.{'dashboard' if traffic == 'dashboard_open' else 'ingest'}"
    cell = {"name": name, "config": config, "traffic": traffic, "chips": 1}
    return bench, cell, cfg, tr
