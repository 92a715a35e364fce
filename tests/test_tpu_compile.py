"""Compile every Pallas kernel for a described TPU v5e at deployment widths.

No chip is attached: the TPU compiler that ships with JAX compiles for a
topology it is only told about, and refuses what the chip would refuse
(block alignment, VMEM budgets, primitives Mosaic cannot lower).  Widths:
m=2048 buckets, 2K=1024 resident rows, N=65536 lanes, Q=4 qs, D=12 slices;
the segmented histogram also at a 4096-key bank's stacked 8192 rows, the
row-sparse grid's scalar-prefetch tables and traced pair count.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every test worker imports
this file), which skips where it cannot be described.  The persistent
compile cache is off here: a described chip's executable cannot be read
back without the chip.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.bank_quantiles import bank_quantiles_pallas
from repro.kernels.bank_range_merge import bank_range_merge_pallas
from repro.kernels.ddsketch_hist import histogram_pallas
from repro.kernels.ddsketch_ingest import ddsketch_ingest_pallas
from repro.kernels.ddsketch_scatter import ddsketch_scatter_pallas
from repro.kernels.ddsketch_seg_hist import segment_histogram_pallas
from repro.kernels.fold_pairs import fold_pairs_pallas
from repro.kernels.ref import MAX_COLLAPSE_LEVEL, BucketSpec

SPEC = BucketSpec()  # alpha=0.01, m=2048
K, M, N, Q, D = 512, 2048, 65536, 4, 12  # 2K = 1024 resident rows
K_TALL = 4096  # keys4096: both sign stores stacked, 8192 rows
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _kernel(name):
    """(fn, argument shapes) of one kernel at the widths above."""
    lanes = lambda dt: ((N,), dt)  # noqa: E731
    return {
        "ddsketch_ingest": (
            lambda x, s, w, lv: ddsketch_ingest_pallas(
                x, s, w, lv, num_segments=K, spec=SPEC, interpret=False),
            [lanes(F32), lanes(I32), lanes(F32), lanes(I32)],
        ),
        "ddsketch_seg_hist": (
            lambda x, s, w, lv: segment_histogram_pallas(
                x, s, w, lv, num_segments=K, spec=SPEC, interpret=False),
            [lanes(F32), lanes(I32), lanes(F32), lanes(I32)],
        ),
        "ddsketch_seg_hist_tall": (
            lambda x, s, w, lv: segment_histogram_pallas(
                x, s, w, lv, num_segments=2 * K_TALL, spec=SPEC, interpret=False),
            [lanes(F32), lanes(I32), lanes(F32), lanes(I32)],
        ),
        "ddsketch_scatter": (
            lambda k, w: ddsketch_scatter_pallas(
                k, w, num_rows=2 * K, num_buckets=M, interpret=False),
            [lanes(I32), lanes(F32)],
        ),
        "ddsketch_hist": (
            lambda x, w, lv: histogram_pallas(x, w, lv, spec=SPEC, interpret=False),
            [lanes(F32), lanes(F32), lanes(I32)],
        ),
        "fold_pairs": (
            lambda c: fold_pairs_pallas(c, spec=SPEC, interpret=False),
            [((2 * K, M), F32)],
        ),
        "bank_quantiles": (
            lambda p, n, z, lo, hi, lv, q, t: bank_quantiles_pallas(
                p, n, z, lo, hi, lv, q, t, interpret=False),
            [((2 * K, M), F32), ((2 * K, M), F32), ((2 * K,), F32),
             ((2 * K,), F32), ((2 * K,), F32), ((2 * K,), I32), ((Q,), F32),
             ((MAX_COLLAPSE_LEVEL + 1, M), F32)],
        ),
        "bank_range_merge": (
            lambda c, d: bank_range_merge_pallas(c, d, spec=SPEC, interpret=False),
            [((D, 2 * K, M), F32), ((D, 2 * K), I32)],
        ),
    }[name]


@pytest.mark.parametrize(
    "name",
    ["ddsketch_ingest", "ddsketch_seg_hist", "ddsketch_scatter", "ddsketch_hist",
     "fold_pairs", "bank_quantiles", "bank_range_merge", "ddsketch_seg_hist_tall"],
)
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()  # raises what the chip would
    assert "tpu_custom_call" in compiled.as_text()
