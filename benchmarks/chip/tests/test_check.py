"""The correctness check fails what it must, at a size the CPU holds.

Each case drives a whole run -- generators, gateway, HTTP server, window,
read-back, reference -- without the harness's look for a chip, with the
timed path sound, switched to the control (bfloat16 counts), or broken
underneath: a step that leaves the state unchanged, half of each batch
left out (the rest weighted double, so the mass stays), an answer altered
where the engine produces it.  One chip has no exchange between chips to
leave out.  Run with ``python -m pytest benchmarks/chip/tests`` (CPU).
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE / "tests"))
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import small  # noqa: E402

pytestmark = pytest.mark.skipif(
    __import__("os").environ.get("JAX_PLATFORMS") != "cpu",
    reason="drives whole runs on the CPU (JAX_PLATFORMS=cpu)")


def stuck_state(win, gw):
    """Each ingest tick leaves the bank as it was (the version still moves)."""
    w = win._w

    def stuck(values, ids, weights):
        w._version += 1

    w._ingest = stuck


def half_batch(win, gw):
    """Every other lane of a tick is dropped; the kept ones weigh double."""
    w = win._w
    orig = w._ingest

    def half(values, ids, weights):
        keep = slice(None, None, 2)
        orig(values[keep], ids[keep], np.full(values[keep].size, 2.0, np.float32))

    w._ingest = half


def altered_answer(win, gw):
    """The engine's per-row quantiles come out 3% high."""
    eng = win._w.engine
    orig = eng.quantiles
    eng.quantiles = lambda bank, qs: orig(bank, qs) * 1.03


def _run(config, traffic, *, variant=None, fault=None, seed=2**33 + 17, capacity=None):
    bench, cell, cfg, tr = small.small_specs(config, traffic)
    if capacity is not None:
        cfg["capacity"] = cfg["hosts"] = capacity
    return harness.run_cell(bench, cell["name"], seed, 3.0, False,
                            t_start=time.monotonic(), specs=(cell, cfg, tr),
                            variant=variant, fault=fault, log=lambda _: None)


@pytest.mark.parametrize("config,traffic", [("svc2048", "ingest_closed"),
                                            ("keys4096", "ingest_closed"),
                                            ("svc2048", "dashboard_open")])
def test_sound_run_is_correct(config, traffic):
    out = _run(config, traffic)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_control_bfloat16_counts_fails():
    # 3 keys: hot buckets pass 256 counts early in the run, where bfloat16
    # counts stop taking small increments, however slowly the CPU ingests
    out = _run("keys4096", "ingest_closed", variant="control", capacity=3)
    assert not out["correct"]
    assert out["checks"]["answers_out_of_bound"]["value"] > 0


@pytest.mark.parametrize("fault", [stuck_state, half_batch, altered_answer],
                         ids=["stuck_state", "half_batch", "altered_answer"])
@pytest.mark.parametrize("config,traffic", [("svc2048", "ingest_closed"),
                                            ("svc2048", "dashboard_open")])
def test_fault_fails(config, traffic, fault):
    out = _run(config, traffic, fault=fault)
    assert not out["correct"]
    c = out["checks"]
    assert c["answers_out_of_bound"]["value"] > 0 or c["empty_mismatch"]["value"] > 0
