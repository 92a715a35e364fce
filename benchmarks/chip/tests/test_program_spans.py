"""The readers of the program's own counters and span totals, on
hand-built runs: the change across the window, and None where nothing
ticked or the program reports no such counter.  Run with ``python -m
pytest benchmarks/chip/tests`` (CPU)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

READERS = ("http_parse_ms_per_post", "gateway_admitted_pct", "gateway_queue_wait_ms",
           "drain_host_ms_per_tick", "engine_ingest_call_ms", "planner_wait_ms",
           "materialize_ms_per_s")


def _t(count, total_s):
    return {"count": count, "total_s": total_s, "self_s": total_s / 2}


def _gateway(ticks, accepted, rejected, wait_s, taken, spans):
    return {"ticks": ticks, "accepted_values": accepted, "rejected_values": rejected,
            "queue_wait_s": wait_s, "batches_taken": taken, "spans": spans}


def _run(before, after, t0=100.0, t_end=151.0):
    return harness.Run(t0=t0, t_end=t_end, seconds=t_end - t0, before=before, after=after)


@pytest.fixture
def window_run():
    before = {
        "gateway": _gateway(10, 8192 * 10, 8192 * 50, 4.0, 10, {
            "http.ingest.parse": _t(60, 0.18),
            "gateway.tick": _t(10, 4.0),
            "engine.ingest": _t(10, 3.9),
            "window.materialize_events": _t(1, 0.05),
        }),
        "planner": {"requests": 5, "spans": {"planner.wait": _t(4, 0.02)}},
    }
    after = {
        "gateway": _gateway(110, 8192 * 810, 8192 * 5650, 324.0, 810, {
            "http.ingest.parse": _t(6460, 19.38),
            "gateway.tick": _t(110, 44.3),
            "engine.ingest": _t(110, 43.4),
            "window.materialize_events": _t(2, 0.10),
        }),
        "planner": {"requests": 25, "spans": {"planner.wait": _t(24, 0.22),
                                              "http.read": _t(20, 0.3)}},
    }
    return _run(before, after)


@pytest.mark.parametrize("name, expect", [
    ("http_parse_ms_per_post", 1e3 * (19.38 - 0.18) / 6400),
    ("gateway_admitted_pct", 100.0 * 800 / (800 + 5600)),
    ("gateway_queue_wait_ms", 1e3 * 320.0 / 800),
    ("drain_host_ms_per_tick", 1e3 * ((44.3 - 4.0) - (43.4 - 3.9)) / 100),
    ("engine_ingest_call_ms", 1e3 * (43.4 - 3.9) / 100),
    ("planner_wait_ms", 1e3 * 0.2 / 20),
    ("materialize_ms_per_s", 1e3 * 0.05 / 51.0),
])
def test_reader_takes_the_change_across_the_window(window_run, name, expect):
    assert harness.load_reader(name)(window_run) == pytest.approx(expect, rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_when_nothing_ticked(name):
    idle = {"gateway": _gateway(10, 100, 0, 1.0, 3, {"gateway.tick": _t(10, 1.0),
                                                     "engine.ingest": _t(10, 0.9)}),
            "planner": {"requests": 0, "spans": {}}}
    assert harness.load_reader(name)(_run(idle, idle)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_for_a_program_without_spans(name):
    """The parent program reports neither spans nor the new counters: the
    readers read nothing and do not raise."""
    bare = {"gateway": {"ticks": 10, "accepted_values": 100, "engine_calls": 10}}
    later = {"gateway": {"ticks": 20, "accepted_values": 900, "engine_calls": 20}}
    assert harness.load_reader(name)(_run(bare, later)) is None


def test_materialize_reads_zero_when_ticks_ran_without_it(window_run):
    g = window_run.after["gateway"]["spans"]
    g["window.materialize_events"] = dict(window_run.before["gateway"]["spans"]
                                          ["window.materialize_events"])
    assert harness.load_reader("materialize_ms_per_s")(window_run) == 0.0
