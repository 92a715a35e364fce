"""Device-tier telemetry: one SketchBank living *inside* the jit'd train step.

This is the paper's fleet-monitoring architecture mapped onto a TPU pod
(DESIGN.md §2): every chip is an "agent" sketching its local shard of each
scalar stream; the full mergeability of DDSketch (Algorithm 4 == per-bucket
'+') is what lets XLA all-reduce the bucket arrays — either explicitly via
``sketch_bank.allreduce`` under shard_map, or implicitly when the
scatter-add of a sharded stream into a replicated bank makes the SPMD
partitioner insert the very same all-reduce.

Streams recorded per step (all are skewed, mean-hiding distributions — the
paper's Figure 2 argument applied to training):

  token_loss  — per-token CE losses (B·S values/step); p99/p50 drives the
                loss-spike guard
  grad_rms    — per-parameter-tensor gradient RMS (one value per tensor)
  act_scale   — per-layer residual-stream RMS
  router_load — MoE: per-(layer, expert) dispatch fractions (load skew)

The state is a **TelemetryBank**: a single ``SketchBank`` with one row per
stream (rows padded to a power of two so nearby stream-set sizes share one
engine geometry).  ``record`` concatenates every stream's values into one
``(values, sketch_ids)`` batch and issues **one** ``ops.bank_histograms``
dispatch per step — the trace no longer unrolls a histogram per stream, and
adding/removing a stream changes the batch, not the number of kernels.
Per-row ``auto_collapse`` levels adapt independently (UDDSketch), exactly
as the per-stream sketches did.

Off the hot path the bank routes through the shared ``SketchEngine``
(``reset_telemetry``: one donated AOT executable zeroes the bank in place
between flush windows), ``quantile_summary`` rides the fused
``bank_quantiles`` query (one cumsum per row answers every stream × q), and
``flush_to_host`` moves the whole bank to the host tier in one transfer.

The bank is an ordinary pytree of f32 arrays: it shards/replicates/donates
like any activation, checkpoints with the model (``telemetry_from_sketches``
migrates pre-bank per-stream checkpoint dicts), and flushes losslessly into
the host tier for windowed aggregation.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.core import jax_sketch, sketch_bank as sbank
from repro.core.jax_sketch import BucketSpec, DeviceSketch
from repro.core.sketch_bank import SketchBank

__all__ = [
    "TelemetryConfig",
    "TelemetryBank",
    "TelemetryState",
    "init_telemetry",
    "record",
    "reset_telemetry",
    "telemetry_engine",
    "telemetry_shardings",
    "quantile_summary",
    "flush_to_host",
    "telemetry_from_sketches",
    "legacy_telemetry_struct",
]

# streams recorded by the train step, in a stable order
TRAIN_STREAMS = ("token_loss", "grad_rms", "act_scale", "router_load")
SERVE_STREAMS = ("decode_latency",)


@dataclass(frozen=True)
class TelemetryConfig:
    spec: BucketSpec = BucketSpec(relative_accuracy=0.01, num_buckets=2048, offset=-1024)
    streams: tuple = TRAIN_STREAMS
    enabled: bool = True
    # Uniform-collapse each stream's row *before* its insert so streams
    # spanning more decades than the static bucket range (e.g. exploding
    # grads) degrade alpha instead of clamping into the edge buckets.
    auto_collapse: bool = False
    # Raise at trace time when ``record`` is handed a stream name outside
    # ``streams`` (typo-proofing); ``strict=False`` restores the old
    # silently-drop behaviour for callers that feed a superset.
    strict: bool = True


@jax.tree_util.register_dataclass
@dataclass
class TelemetryBank:
    """All telemetry streams as one ``SketchBank`` (row i == streams[i]).

    ``streams`` is static pytree metadata (never traced), so the bank jits,
    shards, donates and checkpoints as a plain pytree of arrays while the
    name → row map travels with it.  The bank may carry more rows than
    streams (power-of-two padding, ``engine.tables.padded_row_count``);
    surplus rows stay empty.
    """

    bank: SketchBank
    streams: tuple = field(metadata=dict(static=True))

    @property
    def sketches(self) -> dict:
        """Back-compat per-stream view: row i as a standalone DeviceSketch."""
        return {name: sbank.row(self.bank, i) for i, name in enumerate(self.streams)}


# the pre-bank recorder state was also exported under this name
TelemetryState = TelemetryBank


def _num_rows(streams) -> int:
    from repro.engine.tables import padded_row_count

    return padded_row_count(len(streams))


def init_telemetry(tcfg: TelemetryConfig) -> TelemetryBank:
    return TelemetryBank(
        bank=sbank.empty(tcfg.spec, _num_rows(tcfg.streams)),
        streams=tuple(tcfg.streams),
    )


def telemetry_engine(tcfg: TelemetryConfig):
    """The shared ``SketchEngine`` for this config's bank geometry.

    One engine (and so one set of AOT executables) per (spec, padded row
    count) — every stream set that pads to the same geometry reuses it.
    """
    from repro.engine.engine import shared_engine

    return shared_engine(tcfg.spec, _num_rows(tcfg.streams))


def reset_telemetry(state: TelemetryBank, tcfg: TelemetryConfig) -> TelemetryBank:
    """Zero the bank **in place** for the next flush window (donated).

    One persistent compiled executable call; per-row collapse levels
    survive (a stream that adapted to a wide range stays adapted), exactly
    like ``KeyedWindow.reset``.  The input state is consumed — rebind.
    """
    return TelemetryBank(
        bank=telemetry_engine(tcfg).reset(state.bank), streams=state.streams
    )


def telemetry_shardings(tcfg: TelemetryConfig, mesh: Mesh):
    """Telemetry state is replicated: it is the *result* of the all-reduce
    merge, O(rows·m) floats — negligible (``rules.telemetry_pspec``)."""
    from repro.sharding.rules import telemetry_pspec

    repl = NamedSharding(mesh, telemetry_pspec())
    state = jax.eval_shape(lambda: init_telemetry(tcfg))
    return jax.tree.map(lambda _: repl, state)


def record(
    state: TelemetryBank,
    streams: dict,
    tcfg: TelemetryConfig,
    *,
    strict: bool | None = None,
) -> TelemetryBank:
    """Insert every stream's values in one bank dispatch (Algorithm 1).

    ``streams`` maps stream name -> array of values (any shape; non-finite
    entries are ignored, which also makes masked-out token losses — set to
    NaN by loss_fn — drop out naturally).  All streams concatenate into one
    ``(values, sketch_ids)`` batch and update the bank with a **single**
    ``ops.bank_histograms`` call (one ``segment_histogram`` launch), so
    the traced step carries one histogram no matter how many streams are
    live.

    Unknown stream names raise at trace time (``ValueError``) unless
    ``strict=False`` (argument or ``tcfg.strict``) asks for the legacy
    silently-drop behaviour.
    """
    if not tcfg.enabled:
        return state
    strict = tcfg.strict if strict is None else strict
    unknown = sorted(set(streams) - set(state.streams))
    if unknown and strict:
        raise ValueError(
            f"unknown telemetry stream(s) {unknown}; configured streams are "
            f"{list(state.streams)} — fix the name or pass strict=False"
        )
    vals, ids = [], []
    for i, name in enumerate(state.streams):
        if name not in streams:
            continue
        v = jnp.asarray(streams[name]).reshape(-1)
        if v.size == 0:  # stream not produced (e.g. non-MoE router_load)
            continue
        vals.append(v.astype(jnp.float32))
        ids.append(jnp.full(v.shape, i, jnp.int32))
    if not vals:
        return state
    bank = sbank.add(
        state.bank,
        jnp.concatenate(vals),
        jnp.concatenate(ids),
        spec=tcfg.spec,
        auto_collapse=tcfg.auto_collapse,
    )
    return TelemetryBank(bank=bank, streams=state.streams)


def grad_rms_stream(grads) -> jnp.ndarray:
    """Per-tensor gradient RMS values (the grad_rms stream)."""
    leaves = jax.tree.leaves(grads)
    return jnp.stack(
        [jnp.sqrt(jnp.mean(jnp.square(g.astype(jnp.float32)))) for g in leaves]
    )


def quantile_summary(
    state: TelemetryBank, tcfg: TelemetryConfig, qs=(0.5, 0.95, 0.99)
) -> dict:
    """Jit-friendly per-stream quantiles (used for in-loop guards).

    One fused ``bank_quantiles`` query answers every stream × q off a
    single cumsum per row — no per-stream rebuild, no Python loop over
    sketches.  Bit-exact vs querying each row as a standalone sketch.
    """
    out = sbank.quantiles(state.bank, jnp.asarray(qs, jnp.float32), spec=tcfg.spec)
    return {name: out[i] for i, name in enumerate(state.streams)}


# --------------------------------------------------------------------- #
# host-tier flush + checkpoint migration
# --------------------------------------------------------------------- #
def flush_to_host(state: TelemetryBank, spec: BucketSpec) -> dict:
    """Every stream's row as an exact host ``DDSketch`` (lossless, like
    ``jax_sketch.to_host``), moving the whole bank device->host in one
    pytree transfer instead of one per stream × field."""
    host_bank = jax.tree.map(np.asarray, state.bank)
    return {
        name: jax_sketch.to_host(DeviceSketch(*(f[i] for f in host_bank)), spec)
        for i, name in enumerate(state.streams)
    }


def telemetry_from_sketches(sketches: dict, tcfg: TelemetryConfig) -> TelemetryBank:
    """Stack per-stream ``DeviceSketch``es into a TelemetryBank.

    The checkpoint-migration path: pre-bank checkpoints stored one sketch
    per stream (dict keyed by name).  Rows fill in ``tcfg.streams`` order
    (missing streams stay empty, surplus names are dropped); padding rows
    stay empty.  Per-sketch collapse levels transfer as per-row levels.
    """
    state = init_telemetry(tcfg)
    bank = state.bank
    for i, name in enumerate(state.streams):
        if name not in sketches:
            continue
        sk = DeviceSketch(*(jnp.asarray(f) for f in sketches[name]))
        bank = sbank.set_row(bank, i, sk)
    return TelemetryBank(bank=bank, streams=state.streams)


def legacy_telemetry_struct(tcfg: TelemetryConfig) -> dict:
    """The pre-bank telemetry pytree *structure* (dict of per-stream
    DeviceSketch structs) — what old checkpoints flattened their ``tel``
    entry from; used to re-interpret their leaves before
    ``telemetry_from_sketches`` stacks them into a bank."""
    return {
        "sketches": {
            name: jax.eval_shape(lambda: jax_sketch.empty(tcfg.spec))
            for name in tcfg.streams
        }
    }
