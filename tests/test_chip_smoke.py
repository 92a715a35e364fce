"""``chip_smoke.py`` off the chip: its phases at a tiny size on the CPU with
the TPU kernels in interpret mode (steered by the ``tpu_interpret``
fixture), its refusal to run without a chip, and the chip-only guards it
relies on (peaks keyed by device kind, CPU-child sweeps refused on TPU)."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from util import run_with_devices

ROOT = Path(__file__).resolve().parents[1]


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_clean(rec, ticks):
    assert rec["answers_checked"] > 0 and rec["failed_answers"] == 0
    assert rec["dispatch_fallbacks"] == 0
    assert rec["levels_seen"] == [0, 1]  # the wide keys folded reactively
    gw = rec["gateway"]
    assert gw["engine_calls"] == gw["ticks"] == ticks
    assert gw["ingested_values"] == gw["accepted_values"] == rec["submitted"]
    assert gw["shed_mass"] == 0 and gw["drain_errors"] == 0
    assert rec["max_err_over_alpha_L"] <= 1.0 + 1e-3


def test_fused_phase_tiny_interpret(tpu_interpret):
    res = _smoke().phase_fused(0, capacity=15, num_slices=4, ticks=3,
                               per_tick=1024, windows=("1m", "3m", "4m"),
                               sample_keys=8)
    _assert_clean(res["record"], 3)
    # every path the chip checks for a Mosaic kernel was built
    assert {"ingest", "quantiles", "rollup", "window_query", "window_rollup",
            "slab_merge_node"} <= set(res["record"]["kernels"])


def test_tall_phase_tiny_interpret(tpu_interpret, monkeypatch):
    from repro.core import sketch_bank as sb
    from repro.kernels import ops

    # a 16-row bank stands in for the >512-key path: past the (lowered)
    # resident-row ceiling, ingest takes the segment-histogram kernel
    monkeypatch.setattr(ops, "MAX_RESIDENT_ROWS", 16)
    assert sb.picked_insert_method(2048, 16, 2048) == "matmul"
    res = _smoke().phase_tall(0, capacity=15, ticks=2, per_tick=2048,
                              sample_keys=8)
    _assert_clean(res["record"], 2)


def test_sharded_phase_tiny_on_four_virtual_devices():
    out = run_with_devices(
        f"""
import importlib.util, json
from jax.experimental.pallas import tpu as pltpu
from repro.kernels import ops
spec = importlib.util.spec_from_file_location("chip_smoke", {str(ROOT / "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
ops._on_tpu = lambda: True  # the chip's dispatch, kernels interpreted
pltpu.set_tpu_interpret_mode()
res = smoke.phase_sharded(0, capacity=31, ticks=3, per_tick=2048,
                          num_slices=4, windows=("1m", "3m", "4m"),
                          sample_keys=8)
print(json.dumps(res["record"]))
""",
        n_devices=4,
    )
    import json

    rec = json.loads(out.strip().splitlines()[-1])
    _assert_clean(rec, 3)
    assert rec["shards"] == 4 and rec["one_chip_equal"]["keys"] == rec["keys"]


@pytest.mark.parametrize("alone", [False, True], ids=["in_repo", "script_alone"])
def test_no_chip_exits_nonzero_without_verdict(alone, tmp_path):
    script = ROOT / "chip_smoke.py"
    if alone:  # a directory holding chip_smoke.py and nothing else of the repo
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, env=env, cwd=script.parent, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_peaks_keyed_by_device_kind():
    from repro.launch import roofline

    v5e = roofline.peaks("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")  # a CPU run has no roofline share to write
    terms = roofline.roofline_terms(197e12, 0.0, 0.0, hw=v5e)
    assert terms["compute_s"] == 1.0


def test_cpu_child_sweeps_refuse_on_tpu(monkeypatch):
    import jax

    from benchmarks import bank_bench

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU rows"):
        bank_bench.bench_sharded_ingest()
    with pytest.raises(RuntimeError, match="CPU rows"):
        bank_bench.bench_fleet_ingest()
