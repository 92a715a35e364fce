"""Per-arch smoke tests: every assigned architecture instantiates a reduced
same-family config and runs forward / train-step / prefill+decode on CPU,
asserting output shapes and finiteness (pool requirement)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.models.common import init_params, param_shapes
from repro.models.model import (
    decode_step,
    forward,
    init_cache,
    loss_fn,
    prefill,
)

ARCHS = configs.ARCHS


def _batch(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    if cfg.encoder_layers:
        batch["ctx"] = jnp.asarray(
            rng.standard_normal((B, cfg.encoder_seq, cfg.d_model)), cfg.jdtype
        )
    elif cfg.cross_attn_every:
        batch["ctx"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_cross_tokens, cfg.d_model)), cfg.jdtype
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_loss(arch):
    cfg = configs.smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits, aux = forward(
        params, batch["tokens"], cfg, ctx=batch.get("ctx"), ssm_chunk=16,
        collect_stats=True,
    )
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    loss, laux = loss_fn(params, batch, cfg, ssm_chunk=16, ce_chunk=16,
                         collect_stats=True)
    assert bool(jnp.isfinite(loss))
    tok = laux["token_losses"]
    assert tok.shape == (2, 32)
    assert bool(jnp.isfinite(tok).all())
    assert laux["act_scales"].shape[0] == cfg.n_layers
    if cfg.n_experts:
        assert laux["router_load"].shape[-1] == cfg.n_experts


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    """One full train step (grads + AdamW + telemetry) on the 1-device mesh."""
    from repro.launch.steps import StepConfig, build_train_step
    cfg = configs.smoke(arch)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    scfg = StepConfig(remat=False, ssm_chunk=16, q_block=32, warmup_steps=2,
                      total_steps=10)
    fn, in_sh, out_sh, donate, state_shapes = build_train_step(cfg, mesh, scfg=scfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    from repro.optim import adamw_init
    from repro.telemetry import TelemetryConfig, init_telemetry
    opt = adamw_init(params)
    tel = init_telemetry(TelemetryConfig())
    batch = _batch(cfg)
    with mesh:
        p2, o2, t2, metrics = jax.jit(fn)(params, opt, tel, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).sum()), p2, params),
    )
    assert delta > 0
    # telemetry saw every unmasked token loss
    assert float(t2.sketches["token_loss"].count) == 2 * 32


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode_consistency(arch):
    """Greedy decode after prefill matches teacher-forced forward logits."""
    cfg = configs.smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg, B=2, S=16)
    toks = batch["tokens"]
    lg, cache = prefill(
        params, toks, cfg, max_len=20, ctx=batch.get("ctx"), ssm_chunk=8
    )
    full, _ = forward(params, toks, cfg, ctx=batch.get("ctx"), ssm_chunk=8)
    np.testing.assert_allclose(
        np.asarray(lg, np.float32),
        np.asarray(full[:, -1], np.float32),
        atol=5e-2, rtol=5e-2,
    )
    # one decode step advances pos and returns finite logits
    nxt, cache = decode_step(params, cache, toks[:, :1], cfg)
    assert nxt.shape == (2, cfg.vocab_size)
    assert bool(jnp.isfinite(nxt.astype(jnp.float32)).all())
    assert int(cache["pos"]) == 17


def test_decode_matches_forward_token_by_token():
    """Sequential decode reproduces teacher-forced logits (dense arch)."""
    cfg = configs.smoke("yi-6b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)).astype(np.int32))
    full, _ = forward(params, toks, cfg, ssm_chunk=8)
    cache = init_cache(cfg, 1, 16)
    outs = []
    for t in range(12):
        lg, cache = decode_step(params, cache, toks[:, t : t + 1], cfg)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)  # (1, 12, V)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full, np.float32), atol=5e-2, rtol=5e-2
    )


def test_param_counts_match_pool_labels():
    expect = {
        "xlstm-1.3b": (1.1e9, 1.5e9),
        "smollm-135m": (0.12e9, 0.15e9),
        "yi-6b": (5.5e9, 6.5e9),
        "jamba-v0.1-52b": (48e9, 55e9),
        "llama-3.2-vision-90b": (80e9, 95e9),
        "llama4-maverick-400b-a17b": (380e9, 410e9),
    }
    for arch, (lo, hi) in expect.items():
        n = configs.get(arch).param_count()
        assert lo <= n <= hi, (arch, n)
    # active params: maverick ~17B-class label (a17b)
    assert configs.get("llama4-maverick-400b-a17b").active_param_count() < 20e9


def test_scan_layers_param_layout():
    cfg = configs.get("jamba-v0.1-52b").replace(scan_layers=True)
    shapes = param_shapes(cfg)
    assert len(shapes["blocks"]) == cfg.cycle_len
    # every block leaf carries the n_cycles leading dim
    leaf = jax.tree.leaves(shapes["blocks"][0])[0]
    assert leaf.shape[0] == cfg.n_cycles
