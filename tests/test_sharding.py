"""Sharding rules: logical-axis mapping, divisibility guards, and compiled
multi-device steps for both profiles (subprocess, 8 fake devices)."""

import pytest

from jax.sharding import PartitionSpec as P

from repro.models.common import PSpec
from repro.sharding import rules

from util import run_with_devices


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 2, "model": 4}


class FakePodMesh:
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 2, "model": 4}


def test_tp_param_specs():
    # attention qkv (embed, heads, head_dim): heads->model, embed->data(FSDP)
    spec = rules.param_spec(
        PSpec((64, 8, 16), ("embed", "heads", "head_dim")), "tp", FakeMesh()
    )
    assert spec == P("data", "model")
    # MoE experts (experts, embed, mlp): experts->model, embed->data, mlp
    # can't reuse 'model'
    spec = rules.param_spec(
        PSpec((8, 64, 32), ("experts", "embed", "mlp")), "tp", FakeMesh()
    )
    assert spec == P("model", "data")
    # kv heads replicated (not in tp rules)
    spec = rules.param_spec(
        PSpec((64, 2, 16), ("embed", "kv_heads", "head_dim")), "tp", FakeMesh()
    )
    assert spec == P("data")


def test_divisibility_guard_replicates():
    # whisper's vocab 51865 % 4 != 0 -> vocab dim replicates
    spec = rules.param_spec(
        PSpec((51865, 64), ("vocab", "embed")), "fsdp", FakeMesh()
    )
    assert spec == P(None, "model")
    # neither 63 nor 9 divisible by model=4 -> fully replicated
    spec = rules.param_spec(
        PSpec((63, 9, 16), ("embed", "heads", "head_dim")), "fsdp", FakeMesh()
    )
    assert spec == P()


def test_fsdp_param_specs():
    spec = rules.param_spec(
        PSpec((64, 8, 16), ("embed", "heads", "head_dim")), "fsdp", FakeMesh()
    )
    assert spec == P("model")


def test_no_fsdp_weights_option():
    spec = rules.param_spec(
        PSpec((64, 32), ("embed", "mlp")), "tp", FakeMesh(), fsdp_weights=False
    )
    assert spec == P(None, "model")


def test_activation_specs_guards():
    mesh = FakeMesh()
    # residual batch-sharded; seq-shard over model when enabled & divisible.
    # NOTE: singleton axis tuples are written unwrapped (P("data"), not
    # P(("data",))) — newer jax canonicalizes the two to equality but jax
    # 0.4.x does not, and the rules return the unwrapped form.
    assert rules.activation_spec("residual", (8, 64, 32), "tp", mesh) == P("data")
    assert rules.activation_spec(
        "residual", (8, 64, 32), "tp", mesh, seq_shard=True
    ) == P("data", "model")
    # heads not divisible -> qkv head axis dropped
    assert rules.activation_spec("qkv", (8, 64, 9, 16), "tp", mesh) == P("data")
    assert rules.activation_spec("qkv", (8, 64, 8, 16), "tp", mesh) == P(
        "data", None, "model"
    )
    # batch=1 can't shard over data
    assert rules.activation_spec("kv_cache_sp", (1, 64, 2, 16), "tp", mesh,
                                 sp_decode_axes=("model",)) == P(None, "model")
    # fsdp: batch takes the idle model axis when divisible (256-way DP)...
    assert rules.activation_spec("logits", (8, 64, 128), "fsdp", mesh) == P(
        ("data", "model")
    )
    # ...falls back to sequence (context parallel), then vocab stays whole
    assert rules.activation_spec("logits", (2, 64, 128), "fsdp", mesh) == P(
        "data", "model"
    )
    assert rules.activation_spec("residual", (2, 64, 32), "fsdp", mesh) == P(
        "data", "model"
    )


def test_dp_axes_multi_pod():
    assert rules.dp_axes(FakePodMesh()) == ("pod", "data")
    assert rules.dp_axes(FakeMesh()) == ("data",)


@pytest.mark.parametrize("arch", ["yi-6b", "qwen3-0.6b"])
def test_step_compiles_multidevice(arch):
    """Both profiles compile + run a smoke train step on a (2,4) mesh."""
    script = f"""
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch.steps import build_train_step, StepConfig, _batch_shardings
from repro.models.common import init_params
from repro.optim import adamw_init
from repro.telemetry import TelemetryConfig, init_telemetry

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = configs.smoke("{arch}")
scfg = StepConfig(remat=False, ssm_chunk=16, q_block=32, warmup_steps=2, total_steps=10)
fn, in_sh, out_sh, donate, shapes = build_train_step(cfg, mesh, scfg=scfg)
params = jax.device_put(init_params(jax.random.PRNGKey(0), cfg), in_sh[0])
opt = jax.device_put(adamw_init(params), in_sh[1])
tel = jax.device_put(init_telemetry(TelemetryConfig()), in_sh[2])
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32))
batch = {{"tokens": toks, "labels": toks}}
b_sh = _batch_shardings({{k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}}, mesh)
batch = {{k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}}
with mesh:
    step = jax.jit(fn, in_shardings=(*in_sh, b_sh), out_shardings=out_sh, donate_argnums=donate)
    p, o, t, m = step(params, opt, tel, batch)
    p, o, t, m = step(p, o, t, batch)
assert np.isfinite(float(m["loss"]))
assert float(t.sketches["token_loss"].count) == 2 * 8 * 32
print("multidevice step OK", float(m["loss"]))
"""
    out = run_with_devices(script, 8)
    assert "multidevice step OK" in out


@pytest.mark.xfail(
    reason="XLA-CPU SPMD partitioner check-fails on subgrouped collectives "
    "over auto-sharded operands (spmd_partitioner_util.cc:504; the "
    "b/433785288 family). The compression math itself is validated in "
    "test_optim.py::test_compressed_psum_error_feedback on a fully-manual "
    "mesh.",
    strict=False,
)
def test_grad_compression_step_compiles():
    """int8-pod-compressed train step on a (2,2,2) pod mesh."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch.steps import build_train_step, StepConfig, _batch_shardings
from repro.models.common import init_params
from repro.optim import adamw_init
from repro.telemetry import TelemetryConfig, init_telemetry

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = configs.smoke("yi-6b")
scfg = StepConfig(remat=False, ssm_chunk=16, q_block=32, grad_compress_axis="pod",
                  warmup_steps=2, total_steps=10)
fn, in_sh, out_sh, donate, shapes = build_train_step(cfg, mesh, scfg=scfg)
params = jax.device_put(init_params(jax.random.PRNGKey(0), cfg), in_sh[0])
opt = adamw_init(params)
opt["err"] = jax.tree.map(lambda p: jnp.zeros((2,) + p.shape, jnp.float32), params)
opt = jax.device_put(opt, in_sh[1])
tel = jax.device_put(init_telemetry(TelemetryConfig()), in_sh[2])
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32))
batch = {"tokens": toks, "labels": toks}
b_sh = _batch_shardings({k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}, mesh)
batch = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
with mesh:
    step = jax.jit(fn, in_shardings=(*in_sh, b_sh), out_shardings=out_sh, donate_argnums=donate)
    p, o, t, m = step(params, opt, tel, batch)
assert np.isfinite(float(m["loss"]))
print("compressed step OK", float(m["loss"]))
"""
    out = run_with_devices(script, 8)
    assert "compressed step OK" in out
