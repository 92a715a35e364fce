"""Self-tests of the yardstick: stream copies, work counts, peaks, trace
reduction.  Run with ``python -m pytest benchmarks/chip/tests`` (CPU)."""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import peaks  # noqa: E402
import stream  # noqa: E402
import trace_reduce  # noqa: E402


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ingest_work = _load(HERE / "work" / "ingest.py", "work_ingest")
window_work = _load(HERE / "work" / "window_query.py", "work_window_query")


# ------------------------------------------------------------------ #
# the stream copies equal the program's generators
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
@pytest.mark.parametrize("num_keys", [511, 4095])
def test_zipf_probs_match_chip_smoke(seed, num_keys):
    smoke = _load(ROOT / "chip_smoke.py", "chip_smoke")
    p_ref, perm_ref = smoke._zipf_probs(num_keys, np.random.default_rng(seed))
    p, perm = stream.zipf_probs(num_keys, np.random.default_rng(seed))
    np.testing.assert_array_equal(p, p_ref)
    np.testing.assert_array_equal(perm, perm_ref)


@pytest.mark.parametrize("name", stream.DATASETS)
@pytest.mark.parametrize("seed", [0, 123456789, 3 * 2**31])
def test_datasets_match_program(name, seed):
    from repro.data.datasets import make_dataset

    np.testing.assert_array_equal(stream.make_dataset(name, 5000, seed),
                                  make_dataset(name, 5000, seed))


def test_pool_is_seeded_and_same_work_for_every_seed():
    fam = {"wide_every": 16, "wide_phase": 7, "neg_every": 5, "neg_phase": 1,
           "value_cap": 1e8}
    sizes = {"low": 64, "high": 4096}
    probs = stream.key_probs(511, 11)
    a = stream.make_pool(64, probs, sizes, fam, 11)
    b = stream.make_pool(64, probs, sizes, fam, 11)
    assert a[2] == b[2]
    c = stream.make_pool(64, stream.key_probs(511, 12), sizes, fam, 12)
    # the same multiset of body sizes, in another order
    assert sorted(v.size for v in a[1]) == sorted(v.size for v in c[1])
    assert a[2] != c[2]


def test_body_text_parses_to_the_saved_values():
    import json

    body, parsed = stream.format_body("/k00001", np.asarray([1.5, -2.25e9, 3.14159265]))
    got = np.asarray(json.loads(body)["values"], np.float32)
    np.testing.assert_array_equal(got, parsed)


def test_arrivals_fixed_count_and_span():
    t = stream.arrivals(100.0, 30.0, np.random.default_rng(1))
    assert t.size == 3000
    assert t[0] == 0.0 and t[-1] < 30.0 and np.all(np.diff(t) > 0)


# ------------------------------------------------------------------ #
# work counts against hand counts
# ------------------------------------------------------------------ #
def test_ingest_bytes_two_shapes():
    alpha, off, m = 0.01, -1024, 2048
    # shape 1: three lanes of one row in one bucket, one negative lane
    rows = np.asarray([3, 3, 3, 3])
    vals = np.asarray([1.005, 1.006, 1.007, -1.005], np.float32)
    lvl = np.zeros(4, np.int64)
    u = ingest_work.distinct_cells(rows, vals, alpha, lvl, off, m)
    assert u == 2  # (3, +, key 1) and (3, -, key 1)
    assert ingest_work.needed_bytes(4, u) == 8 * 4 + 8 * 2
    # shape 2: two rows, values a bucket apart, one row folded once
    gamma = (1 + alpha) / (1 - alpha)
    vals = np.asarray([gamma ** 10 * 0.999, gamma ** 11 * 0.999,
                       gamma ** 10 * 0.999, gamma ** 11 * 0.999])
    rows = np.asarray([0, 0, 1, 1])
    lvl = np.asarray([0, 0, 1, 1])
    # row 0: keys 10 and 11; row 1 at level 1: ceil(10/2) = ceil(11/2)... 5, 6
    assert ingest_work.distinct_cells(rows, vals, alpha, lvl, off, m) == 4
    lvl = np.asarray([0, 0, 2, 2])  # ceil(10/4) = ceil(11/4) = 3
    assert ingest_work.distinct_cells(rows, vals, alpha, lvl, off, m) == 3


def test_window_cover_two_shapes():
    # 64-slice ring, 68 sealed, window of 5 slices: sealed [64, 68) is one
    # aligned block of 4 -> one node; plus the live bank
    assert window_work.cover_size(68, 5, 64) == 1
    assert window_work.needed_bytes(68, 5, 64, 512, 2048) == 2 * 512 * 2 * 2048 * 4
    # at 70 sealed, [66, 70) is two aligned blocks of 2
    assert window_work.cover_size(70, 5, 64) == 2
    # window of 60 slices at 67 sealed: [8, 67) = 8..15 (8), 16..31 (16),
    # 32..63 (32), 64..65 (2), 66 (1) -> 5 nodes
    assert window_work.cover_size(67, 60, 64) == 5
    assert window_work.needed_bytes(67, 60, 64, 512, 2048) == 6 * 512 * 2 * 2048 * 4


def test_window_cover_matches_program_ring_math():
    from repro.engine.ring import WindowRing

    class _Engine:
        def new_slab(self, n):
            return None

    ring = WindowRing(_Engine(), 64)
    for sealed in (64, 65, 67, 80, 127, 128, 200):
        for w in (2, 5, 60, 64):
            span = min(w - 1, sealed)
            want = len(ring.range_nodes_at(sealed, sealed - span, sealed))
            assert window_work.cover_size(sealed, w, 64) == want


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# ------------------------------------------------------------------ #
# trace reduction
# ------------------------------------------------------------------ #
def test_summarise_hand_built_trace():
    ms = 1_000_000
    raw = {
        "chips": {0: {
            "ops": [("fusion.1", 1 * ms, 3 * ms), ("fusion.2", 2 * ms, 4 * ms),
                    ("copy.3", 6 * ms, 7 * ms), ("fusion.1", 20 * ms, 21 * ms)],
            "modules": [("jit_ingest_impl(12)", 1 * ms, 4 * ms),
                        ("jit_copy_impl(3)", 6 * ms, 7 * ms)],
        }},
        "spans": [("bench.window_start", 0, 1), ("bench.window_end", 10 * ms, 10 * ms + 1),
                  ("bench.record_batches", int(0.5 * ms), 5 * ms),
                  ("bench.publish", int(5.5 * ms), int(7.5 * ms))],
    }
    out = trace_reduce.summarise(raw)
    assert out["window_s"] == pytest.approx(0.01)
    assert out["busy_s"] == pytest.approx(0.004)  # [1, 4) and [6, 7); 20 ms is outside
    assert out["module_s"]["jit_ingest_impl"] == pytest.approx(0.003)
    assert out["in_span_s"]["bench.publish|jit_copy_impl"] == pytest.approx(0.001)
    # gaps [7, 10), [4, 6), [0, 1), each named by the span overlapping most
    assert out["breakdown"]["idle_gaps"] == [
        ["bench.publish", pytest.approx(0.003)],
        ["bench.record_batches", pytest.approx(0.002)],
        ["bench.record_batches", pytest.approx(0.001)]]
    raw["spans"] = raw["spans"][:2]
    assert trace_reduce.summarise(raw)["breakdown"]["idle_gaps"][0][0] == "no bench span"
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["jit_ingest_impl/fusion.1"] == pytest.approx(0.002)
    assert ops["jit_copy_impl/copy.3"] == pytest.approx(0.001)


def test_recorded_chip_trace():
    """A short trace recorded on a TPU v5e (``tests/data/chip_trace.xplane.pb``):
    the reduction finds the chip, the window marks and the engine's modules."""
    out = trace_reduce.reduce(HERE / "tests" / "data" / "chip_trace.xplane.pb")
    assert out["chips"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["module_s"].get("jit_ingest_impl", 0) > 0
    assert out["module_s"].get("jit_copy_impl", 0) > 0
    assert out["in_span_s"].get("bench.window_query|jit_query_impl", 0) > 0
    assert out["in_span_s"].get("bench.window_rollup|jit_rollup_impl", 0) > 0
    assert out["breakdown"]["device_ops"][0][0] == "jit_ingest_impl/ddsketch_ingest_pallas.1"


def test_trace_readers_on_the_recorded_trace():
    """The trace-based readers on the recorded chip trace: shares inside
    (0, 100], nothing where there is nothing to read."""
    sys.path.insert(0, str(HERE / "metrics"))
    import harness

    summary = trace_reduce.reduce(HERE / "tests" / "data" / "chip_trace.xplane.pb")
    cfg = {"relative_accuracy": 0.01, "bucket_offset": -1024, "num_buckets": 2048,
           "num_slices": 64}
    vals = np.linspace(1.0, 50.0, 20000).astype(np.float32)
    bodies = harness.reference.Bodies()
    bodies.add("/k00000", vals)
    bid = harness.proxy.batch_id("/k00000", vals)
    t0, t1 = 0.0, 1.0
    run = harness.Run(trace=summary, config=cfg, t0=t0, t_end=t1, rows=512,
                      device_kind="TPU v5 lite", bodies=bodies,
                      key_row={"/k00000": 0}, key_level=np.zeros(1, np.int64),
                      log=[("ingest", 1, 0.5, 0.6, [bid])] * 5,
                      reads=[("window_query", 70, 5, 0.5, 0.6),
                             ("window_rollup", 70, 60, 0.5, 0.6)] * 5)
    for name in ("ingest_step_roofline", "window_query_roofline",
                 "publish_device_ms_per_s", "device_idle_pct.ingest"):
        value = harness.load_reader(name)(run)
        assert value is not None and value > 0, name
        if name.endswith("roofline") or name.startswith("device_idle"):
            assert value <= 100, (name, value)
    run.trace = None
    assert harness.load_reader("ingest_step_roofline")(run) is None


# ------------------------------------------------------------------ #
# the window's ingest ticks from the state log
# ------------------------------------------------------------------ #
def test_tick_profile_counts_subtile_ticks():
    import harness

    def tick(t, lanes):
        return ("ingest", 0, t, t + 0.01, [("/k00000", n, 1.0, 1.0, 1.0) for n in lanes])

    log = [tick(0.5, [8192]),                        # before the window
           tick(1.0, [8192, 8192]), tick(1.1, [1]),  # 1 lane pads to 32
           ("seal", 0, 1.2, 1.5, ()),
           tick(1.6, [1024]), tick(1.7, [1025]),     # pad to 1024 and 2048
           tick(3.0, [1])]                           # after the window
    run = harness.Run(config={"kernel_tile_lanes": 2048}, log=log, t0=1.0, t_end=2.0)
    out = harness._tick_profile(run)
    assert out["ingest_ticks"] == 4
    assert out["subtile_ticks"] == 2
    longest = out["longest"][0]
    assert longest[0] == "seal" and longest[2] == pytest.approx(0.3)
    assert [r[4] for r in out["longest"] if r[0] == "ingest"] == [2, 3, 1, 2]
    assert harness._pad_lanes(1) == 32 and harness._pad_lanes(2048) == 2048
    assert harness._pad_lanes(2049) == 4096
    probes = [({"kind": "probes"}, 0, {}, None),
              ({"kind": "ingest_open", "values_per_post": {"low": 8192}}, 0, {}, None)]
    assert harness._smallest_post(probes) == 1
    assert harness._smallest_post(probes[1:]) == 8192
