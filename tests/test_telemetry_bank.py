"""TelemetryBank: the train-step recorder as one SketchBank.

Covers the engine-driven telemetry tier's contract:

* a jit'd step records all TRAIN_STREAMS with exactly **one** bank-histogram
  dispatch (trace count asserted, at record level and through the full
  train step);
* quantile summaries are bit-exact vs the pre-bank per-stream path
  (hypothesis sweep across all four TRAIN_STREAMS);
* checkpoints round-trip at nonzero per-row collapse levels, and legacy
  checkpoints holding per-stream sketch dicts still load (migration);
* strict stream-name validation (typo-proofing) with the strict=False
  escape hatch;
* the donated engine reset zeroes counts in place while levels survive.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st

from repro.checkpoint import CheckpointManager
from repro.core import jax_sketch
from repro.kernels import ops
from repro.telemetry import (
    TelemetryBank,
    TelemetryConfig,
    init_telemetry,
    quantile_summary,
    record,
    reset_telemetry,
)
from repro.telemetry.device import (
    TRAIN_STREAMS,
    flush_to_host,
    legacy_telemetry_struct,
    telemetry_from_sketches,
)

QS = (0.0, 0.01, 0.25, 0.5, 0.75, 0.95, 0.99, 1.0)


def _streams(rng, sizes=(257, 13, 7, 33)):
    """One value array per TRAIN_STREAM (odd sizes -> fresh trace caches)."""
    return {
        "token_loss": (rng.pareto(1.0, sizes[0]) + 1.0).astype(np.float32),
        "grad_rms": (10.0 ** rng.uniform(-4, 1, sizes[1])).astype(np.float32),
        "act_scale": rng.normal(1.0, 0.3, sizes[2]).astype(np.float32),
        "router_load": rng.random(sizes[3]).astype(np.float32),
    }


class _HistCounter:
    """Counts ops.bank_histograms invocations (i.e. traced dispatches)."""

    def __init__(self, monkeypatch):
        self.calls = 0
        orig = ops.bank_histograms

        def counted(*args, **kwargs):
            self.calls += 1
            return orig(*args, **kwargs)

        monkeypatch.setattr(ops, "bank_histograms", counted)


# --------------------------------------------------------------------- #
# trace counts: all streams, one dispatch
# --------------------------------------------------------------------- #
def test_record_single_hist_dispatch(rng, monkeypatch):
    jax.clear_caches()  # a warm nested-jit cache would absorb the trace
    counter = _HistCounter(monkeypatch)
    tcfg = TelemetryConfig()
    state = init_telemetry(tcfg)
    streams = {k: jnp.asarray(v) for k, v in _streams(rng, (251, 11, 5, 29)).items()}
    jax.eval_shape(
        lambda s, vs: record(s, vs, tcfg), state, streams
    )  # trace without compiling
    assert counter.calls == 1, "record must fuse every stream into one dispatch"


def test_train_step_single_hist_dispatch(monkeypatch):
    """The acceptance criterion: tracing a full jit'd train step issues
    exactly one bank-histogram call for all TRAIN_STREAMS."""
    from repro import configs
    from repro.launch.steps import StepConfig, build_train_step

    jax.clear_caches()  # other tests trace smoke steps; a warm nested-jit
    # cache would absorb the add trace this test wants to observe
    counter = _HistCounter(monkeypatch)
    cfg = configs.smoke("smollm-135m")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    scfg = StepConfig(remat=False, ssm_chunk=16, q_block=32, warmup_steps=2,
                      total_steps=10)
    with mesh:
        fn, _, _, _, state_shapes = build_train_step(cfg, mesh, scfg=scfg)
        toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        jax.eval_shape(fn, *state_shapes, {"tokens": toks, "labels": toks})
    assert counter.calls == 1, (
        f"train step traced {counter.calls} bank-histogram calls; "
        "all TRAIN_STREAMS must share one"
    )


# --------------------------------------------------------------------- #
# bit-exactness vs the pre-bank per-stream path
# --------------------------------------------------------------------- #
def _dict_path_quantiles(streams, tcfg, qs):
    """The old recorder: one jax_sketch.add + quantiles per stream."""
    out = {}
    for name in TRAIN_STREAMS:
        sk = jax_sketch.empty(tcfg.spec)
        sk = jax_sketch.add(
            sk, jnp.asarray(streams[name]), spec=tcfg.spec,
            auto_collapse=tcfg.auto_collapse,
        )
        out[name] = np.asarray(
            jax_sketch.quantiles(sk, jnp.asarray(qs, jnp.float32), spec=tcfg.spec)
        )
    return out

def test_bank_vs_dict_bit_exact(rng):
    tcfg = TelemetryConfig()
    streams = _streams(rng)
    state = record(init_telemetry(tcfg), streams, tcfg)
    bank_q = quantile_summary(state, tcfg, QS)
    dict_q = _dict_path_quantiles(streams, tcfg, QS)
    for name in TRAIN_STREAMS:
        np.testing.assert_array_equal(np.asarray(bank_q[name]), dict_q[name])


@settings(deadline=None, max_examples=25)
@given(
    seed=st.integers(0, 2**31 - 1),
    sizes=st.tuples(*(st.integers(1, 64) for _ in range(4))),
    decades=st.floats(0.5, 12.0),
    auto_collapse=st.booleans(),
)
def test_bank_vs_dict_bit_exact_sweep(seed, sizes, decades, auto_collapse):
    """Hypothesis sweep: every TRAIN_STREAM, every q, arbitrary widths —
    the bank path answers bit-identically to four standalone sketches
    (including mixed per-row collapse levels under auto_collapse)."""
    rng = np.random.default_rng(seed)
    tcfg = TelemetryConfig(auto_collapse=auto_collapse)
    streams = {
        name: (10.0 ** rng.uniform(-decades, decades, n)).astype(np.float32)
        * np.where(rng.random(n) < 0.25, -1.0, 1.0).astype(np.float32)
        for name, n in zip(TRAIN_STREAMS, sizes)
    }
    state = record(init_telemetry(tcfg), streams, tcfg)
    bank_q = quantile_summary(state, tcfg, QS)
    dict_q = _dict_path_quantiles(streams, tcfg, QS)
    for name in TRAIN_STREAMS:
        np.testing.assert_array_equal(np.asarray(bank_q[name]), dict_q[name])


# --------------------------------------------------------------------- #
# strict stream names
# --------------------------------------------------------------------- #
def test_unknown_stream_raises(rng):
    tcfg = TelemetryConfig()
    state = init_telemetry(tcfg)
    with pytest.raises(ValueError, match="token_losss"):
        record(state, {"token_losss": jnp.ones(3)}, tcfg)
    # escape hatch: argument-level ...
    state2 = record(state, {"token_losss": jnp.ones(3)}, tcfg, strict=False)
    assert float(state2.bank.counts.sum()) == 0  # dropped, not recorded
    # ... and config-level
    lenient = TelemetryConfig(strict=False)
    state3 = record(init_telemetry(lenient), {"nope": jnp.ones(3)}, lenient)
    assert float(state3.bank.counts.sum()) == 0
    # raising happens at trace time, before any device work
    with pytest.raises(ValueError):
        jax.eval_shape(
            lambda s: record(s, {"typo": jnp.ones(3)}, tcfg), state
        )


# --------------------------------------------------------------------- #
# checkpoint round-trips (new format at nonzero levels, legacy dicts)
# --------------------------------------------------------------------- #
def _wide_state(rng, tcfg):
    """Recorded state whose token_loss row collapsed to a nonzero level."""
    streams = _streams(rng)
    streams["token_loss"] = (10.0 ** rng.uniform(-15, 9, 400)).astype(np.float32)
    return record(init_telemetry(tcfg), streams, tcfg), streams


def test_checkpoint_roundtrip_nonzero_levels(rng, tmp_path):
    tcfg = TelemetryConfig(auto_collapse=True)
    state, _ = _wide_state(rng, tcfg)
    levels = np.asarray(state.bank.level)
    assert levels.max() >= 1, "the 24-decade stream must have collapsed"

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, {"tel": state})
    like = {"tel": jax.eval_shape(lambda: init_telemetry(tcfg))}
    step, restored, _ = mgr.restore(like)
    assert step == 3
    rt = restored["tel"]
    assert isinstance(rt, TelemetryBank) and rt.streams == state.streams
    np.testing.assert_array_equal(np.asarray(rt.bank.level), levels)
    want = quantile_summary(state, tcfg, QS)
    got = quantile_summary(
        TelemetryBank(bank=jax.tree.map(jnp.asarray, rt.bank), streams=rt.streams),
        tcfg,
        QS,
    )
    for name in TRAIN_STREAMS:
        np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(want[name]))


def test_legacy_dict_checkpoint_loads(rng, tmp_path):
    """Pre-bank checkpoints stored one DeviceSketch dict per stream; the
    migration hook restacks their leaves into a TelemetryBank losslessly."""
    tcfg = TelemetryConfig(auto_collapse=True)
    streams = _streams(rng)
    streams["grad_rms"] = (10.0 ** rng.uniform(-15, 9, 200)).astype(np.float32)
    legacy = {
        "sketches": {
            name: jax_sketch.add(
                jax_sketch.empty(tcfg.spec), jnp.asarray(v), spec=tcfg.spec,
                auto_collapse=True,
            )
            for name, v in streams.items()
        }
    }
    assert int(legacy["sketches"]["grad_rms"].level) >= 1

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, {"tel": legacy})

    def migrate(paths, leaves, like):
        legacy_like = {"tel": legacy_telemetry_struct(tcfg)}
        state = jax.tree.unflatten(jax.tree.structure(legacy_like), leaves)
        return {"tel": telemetry_from_sketches(state["tel"]["sketches"], tcfg)}

    like = {"tel": jax.eval_shape(lambda: init_telemetry(tcfg))}
    # without the migrator the structure mismatch must still raise
    with pytest.raises(ValueError):
        mgr.restore(like)
    step, restored, _ = mgr.restore(like, migrate=migrate)
    assert step == 7
    bank_state = restored["tel"]
    assert isinstance(bank_state, TelemetryBank)
    hosts = flush_to_host(bank_state, tcfg.spec)
    for name, v in streams.items():
        direct = jax_sketch.to_host(legacy["sketches"][name], tcfg.spec)
        assert hosts[name].count == direct.count
        for q in (0.1, 0.5, 0.99):
            assert hosts[name].quantile(q) == pytest.approx(
                direct.quantile(q), rel=1e-6
            )


def test_train_loop_migrates_legacy_checkpoint(rng, tmp_path):
    """End to end: a checkpoint written with the dict-of-sketches layout
    resumes into the TelemetryBank train loop."""
    from repro import configs
    from repro.launch.train import TrainLoop

    cfg = configs.smoke("smollm-135m")
    loop = TrainLoop(cfg, batch=4, seq=32, steps=6,
                     ckpt_dir=str(tmp_path / "c"), ckpt_every=5, flush_every=5)
    # forge a step-5 checkpoint whose tel entry uses the legacy layout
    params, opt, tel, _ = loop.init_or_restore()
    legacy_tel = {
        "sketches": {
            name: jax_sketch.add(
                jax_sketch.empty(loop.tcfg.spec),
                jnp.asarray((rng.pareto(1.0, 50) + 1.0).astype(np.float32)),
                spec=loop.tcfg.spec,
            )
            for name in loop.tcfg.streams
        }
    }
    loop.ckpt.save(5, {"params": params, "opt": opt, "tel": legacy_tel},
                   aux={"data": {"seed": loop.data.seed, "next_index": 5}})
    out = loop.run()  # resumes from 5, runs to 6
    assert len(out["metrics"]) == 1
    assert np.isfinite(out["final_loss"])


# --------------------------------------------------------------------- #
# engine-routed reset
# --------------------------------------------------------------------- #
def test_reset_preserves_levels_and_zeroes_counts(rng):
    tcfg = TelemetryConfig(auto_collapse=True)
    state, _ = _wide_state(rng, tcfg)
    levels = np.asarray(state.bank.level).copy()
    assert levels.max() >= 1
    assert float(np.asarray(state.bank.counts).sum()) > 0
    state = reset_telemetry(state, tcfg)  # donated: old state is consumed
    assert float(np.asarray(state.bank.counts).sum()) == 0
    np.testing.assert_array_equal(np.asarray(state.bank.level), levels)
    assert np.all(np.isinf(np.asarray(state.bank.vmin)))
    # the next window records into the reset bank at the surviving levels
    state = record(state, {"token_loss": jnp.ones(5)}, tcfg)
    assert float(state.sketches["token_loss"].count) == 5
