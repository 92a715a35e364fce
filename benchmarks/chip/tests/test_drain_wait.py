"""The reader of the drain loop's wait between ticks, on hand-built runs:
the change across the window, and None where nothing ticked or the
program reports no such counter.  Run with ``python -m pytest
benchmarks/chip/tests`` (CPU)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def _run(before, after):
    return harness.Run(t0=100.0, t_end=151.0, seconds=51.0, before=before, after=after)


def _gateway(ticks, wait_s):
    return {"gateway": {"ticks": ticks, "drain_wait_s": wait_s}}


@pytest.mark.parametrize("before, after, expect", [
    (_gateway(10, 0.5), _gateway(1810, 0.95), 1e3 * 0.45 / 1800),
    (_gateway(10, 0.5), _gateway(10, 0.5), None),
    ({"gateway": {"ticks": 10}}, {"gateway": {"ticks": 1810}}, None),
], ids=["window", "nothing_ticked", "program_without_the_counter"])
def test_drain_wait_ms_per_tick(before, after, expect):
    got = harness.load_reader("drain_wait_ms_per_tick")(_run(before, after))
    if expect is None:
        assert got is None
    else:
        assert got == pytest.approx(expect, rel=1e-12)
