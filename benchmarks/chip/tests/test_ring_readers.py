"""The ring's readers (``ring_read_lock_ms``, ``ring_seal_ms``) on
hand-built runs, and the ring cell's sound run at a size the CPU holds.
Run with ``python -m pytest benchmarks/chip/tests`` (CPU)."""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE / "tests"))
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import small  # noqa: E402

READERS = ("ring_read_lock_ms", "ring_seal_ms")


def _t(count, total_s):
    return {"count": count, "total_s": total_s, "self_s": total_s}


def _run(before_spans, after_spans):
    before = {"gateway": {"ticks": 10, "spans": before_spans}}
    after = {"gateway": {"ticks": 90, "spans": after_spans}}
    return harness.Run(t0=100.0, t_end=151.0, seconds=51.0, before=before, after=after)


@pytest.mark.parametrize("name, span, expect", [
    ("ring_read_lock_ms", "window.ring_read", 1e3 * (0.5 - 0.02) / 120),
    ("ring_seal_ms", "window.ring_seal", 1e3 * (0.9 - 0.15) / 5),
])
def test_reader_takes_the_change_across_the_window(name, span, expect):
    spans0 = {"window.ring_read": _t(8, 0.02), "window.ring_seal": _t(1, 0.15)}
    spans1 = {"window.ring_read": _t(128, 0.5), "window.ring_seal": _t(6, 0.9)}
    assert harness.load_reader(name)(_run(spans0, spans1)) == pytest.approx(expect, rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_when_no_read_or_seal_fell_in_the_window(name):
    spans = {"window.ring_read": _t(8, 0.02), "window.ring_seal": _t(1, 0.15),
             "gateway.tick": _t(10, 1.0)}
    later = dict(spans, **{"gateway.tick": _t(90, 9.0)})
    assert harness.load_reader(name)(_run(spans, later)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_for_a_program_without_the_span(name):
    """A program without the ring's spans (the parent of the change that
    adds them): the readers read nothing and do not raise."""
    spans = {"gateway.tick": _t(10, 1.0), "window.advance_slice": _t(2, 0.4)}
    later = {"gateway.tick": _t(90, 9.0), "window.advance_slice": _t(7, 1.4)}
    assert harness.load_reader(name)(_run(spans, later)) is None
    bare = {"gateway": {"ticks": 10}}
    assert harness.load_reader(name)(harness.Run(before=bare, after=bare)) is None


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="drives a whole run on the CPU (JAX_PLATFORMS=cpu)")
def test_ring_cell_sound_run_is_correct():
    """The ring cell's stack, small: seals inside the window, windowed
    reads at both of the configuration's windows, every answer checked."""
    bench, cell, cfg, tr = small.small_specs("ring4096", "ingest_closed_reads")
    cell["name"] = "ring4096.ingest_reads"
    logs = []
    out = harness.run_cell(bench, cell["name"], 2**33 + 29, 4.0, False,
                           t_start=time.monotonic(), specs=(cell, cfg, tr),
                           log=logs.append)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["metrics"]["ingest_values_per_s"]["value"] > 0
    detail = json.loads(logs[-1])
    assert detail["gateway"]["slice_advances"] >= 2
    assert detail["answers_checked"] > 0
