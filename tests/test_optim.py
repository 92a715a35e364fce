"""Optimizer substrate: AdamW reference equivalence, schedule, clipping,
ZeRO-1 spec placement, int8 error-feedback compression."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    cosine_schedule,
    global_norm,
)
from repro.optim.adamw import _zero1_spec

from util import run_with_devices


def _np_adamw(p, g, m, v, t, cfg: AdamWConfig, lr):
    m = cfg.b1 * m + (1 - cfg.b1) * g
    v = cfg.b2 * v + (1 - cfg.b2) * g * g
    mh = m / (1 - cfg.b1**t)
    vh = v / (1 - cfg.b2**t)
    delta = mh / (np.sqrt(vh) + cfg.eps)
    if p.ndim >= cfg.decay_min_ndim:
        delta = delta + cfg.weight_decay * p
    return p - lr * delta, m, v


def test_adamw_matches_numpy_reference(rng):
    cfg = AdamWConfig()
    p = {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
         "b": jnp.asarray(rng.standard_normal((4,)), jnp.float32)}
    state = adamw_init(p, cfg)
    np_p = {k: np.asarray(v) for k, v in p.items()}
    np_m = {k: np.zeros_like(v) for k, v in np_p.items()}
    np_v = {k: np.zeros_like(v) for k, v in np_p.items()}
    lr = 1e-2
    for t in range(1, 4):
        g = {k: rng.standard_normal(v.shape).astype(np.float32) for k, v in np_p.items()}
        p, state = adamw_update({k: jnp.asarray(v) for k, v in g.items()}, state, p, lr, cfg)
        for k in np_p:
            np_p[k], np_m[k], np_v[k] = _np_adamw(np_p[k], g[k], np_m[k], np_v[k], t, cfg, lr)
    for k in np_p:
        np.testing.assert_allclose(np.asarray(p[k]), np_p[k], rtol=2e-5, atol=2e-6)
    assert int(state["step"]) == 3


def test_weight_decay_skips_vectors(rng):
    cfg = AdamWConfig(weight_decay=1.0)
    p = {"w": jnp.ones((4, 4)), "norm": jnp.ones((4,))}
    state = adamw_init(p, cfg)
    zeros = jax.tree.map(jnp.zeros_like, p)
    p2, _ = adamw_update(zeros, state, p, 0.1, cfg)
    assert float(jnp.abs(p2["w"] - p["w"]).max()) > 0  # decayed
    assert float(jnp.abs(p2["norm"] - p["norm"]).max()) == 0  # not decayed


def test_bf16_moments():
    cfg = AdamWConfig(moment_dtype=jnp.bfloat16)
    p = {"w": jnp.ones((4, 4))}
    state = adamw_init(p, cfg)
    assert state["m"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.full((4, 4), 0.5)}
    _, state = adamw_update(g, state, p, 1e-2, cfg)
    assert state["v"]["w"].dtype == jnp.bfloat16


def test_cosine_schedule():
    kw = dict(peak_lr=1.0, warmup_steps=10, total_steps=110, final_frac=0.1)
    assert float(cosine_schedule(0, **kw)) == pytest.approx(0.1)  # never 0
    assert float(cosine_schedule(4, **kw)) == pytest.approx(0.5)
    assert float(cosine_schedule(10, **kw)) == pytest.approx(1.0)
    assert float(cosine_schedule(110, **kw)) == pytest.approx(0.1)
    mid = float(cosine_schedule(60, **kw))
    assert 0.1 < mid < 1.0


def test_clip_by_global_norm(rng):
    g = {"a": jnp.asarray(rng.standard_normal((16,)), jnp.float32) * 100}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    small = {"a": jnp.asarray([1e-3, 1e-3], jnp.float32)}
    out, _ = clip_by_global_norm(small, 1.0)
    np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(small["a"]))


def test_zero1_spec_adds_data_axis():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 2}

    spec = _zero1_spec(P(None, "model"), (16, 8), FakeMesh())
    assert spec == P("data", "model")
    # already data-sharded params unchanged (tp 2D weights)
    spec2 = _zero1_spec(P("data", "model"), (16, 8), FakeMesh())
    assert spec2 == P("data", "model")
    # indivisible dims stay replicated
    spec3 = _zero1_spec(P(), (3, 5), FakeMesh())
    assert spec3 == P()


def test_compressed_psum_error_feedback():
    """int8 psum over a mesh axis: biased per-step, unbiased across steps
    (error feedback), and exact for representable values."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.optim.compression import compressed_psum, compress_state_init
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("pod",))

def step(g_all, err):
    def inner(g, e):
        e0 = jax.tree.map(lambda x: x[0], e)
        out, e2 = compressed_psum(g, e0, "pod")
        return out, jax.tree.map(lambda x: x[None], e2)
    return shard_map(inner, mesh=mesh,
        in_specs=(P("pod"), P("pod")), out_specs=(P(), P("pod")),
        axis_names={"pod"}, check_vma=False)(g_all, err)

rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
err = jnp.zeros((8, 1, 64), jnp.float32)
ref_mean = np.asarray(g).reshape(8, 64).mean(0)

total = np.zeros(64)
STEPS = 50
for t in range(STEPS):
    out, err = jax.jit(step)(g, err)
    out0 = np.asarray(out).reshape(-1)
    assert np.abs(out0 - ref_mean).max() <= np.abs(ref_mean).max() / 64, "per-step error too large"
    total += out0
# error feedback: time-average converges to the true mean much tighter
drift = np.abs(total / STEPS - ref_mean).max()
assert drift < np.abs(ref_mean).max() / 500, drift
print("compression OK", drift)
"""
    out = run_with_devices(script, 8)
    assert "compression OK" in out
