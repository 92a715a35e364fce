#!/usr/bin/env python3
"""Compile a configuration's engine executables for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse_compile.py svc2048
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 benchmarks/chip/rehearse_compile.py svc2048 --capacity 8191 --shards 4

No chip is attached and nothing runs on one.  The configuration's window
is driven through the set-up a run makes (key rows, one prefill slice, a
seal, the ingest ladder, the planner's reads) with every engine
executable intercepted (on a sharded bank, all but the fleet rollups,
which keep an executable cache of their own): it is lowered against a described ``v5e:2x2``
(one chip, or a ``keys`` mesh over the four) and compiled by the TPU
compiler, its ``memory_analysis()`` kept, and zeros of its output shapes
handed back so the host logic goes on.  Prints one JSON line per
executable and a last line with the largest per-chip footprint.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--capacity", type=int, default=None)
    ap.add_argument("--shards", type=int, default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    import harness
    from repro.engine import engine as engine_mod
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    ops._on_tpu = lambda: True  # trace the kernels the chip would run
    config = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    traffic = json.loads((HERE / "traffic" / "dashboard_open.json").read_text())
    if args.capacity is not None:
        probes = sum(s.get("probe_keys", 0) for s in traffic["streams"])
        config["capacity"] = args.capacity
        config["hosts"] = args.capacity - probes
    records = []

    def compiled(self, key, build, donate, in_kinds, out_kinds, *xs):
        if args.shards:
            from repro.engine.sharded import BANK_ROW_AXIS

            mesh = Mesh(np.asarray(topo.devices[: args.shards]), (BANK_ROW_AXIS,))
            cpu_mesh, self.mesh = self.mesh, mesh
            try:
                fn = self._wrap(build, donate, in_kinds, out_kinds)
            finally:
                self.mesh = cpu_mesh
            shard = {k: NamedSharding(mesh, p) for k, p in _pspecs().items()}
            abstract = [jax.tree.map(lambda a, k=k: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=shard[k]), x) for k, x in zip(in_kinds, xs)]
        else:
            one = SingleDeviceSharding(topo.devices[0])
            fn = self._wrap(build, donate, in_kinds, out_kinds)
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), xs)
        exe = fn.lower(*abstract).compile()
        ma = exe.memory_analysis()
        rec = {"key": [str(k) for k in key],
               "kernels": "tpu_custom_call" in exe.as_text(),
               "argument_bytes": ma.argument_size_in_bytes,
               "output_bytes": ma.output_size_in_bytes,
               "alias_bytes": ma.alias_size_in_bytes,
               "temp_bytes": ma.temp_size_in_bytes}
        records.append(rec)
        print(json.dumps(rec), flush=True)
        out = jax.eval_shape(build, *xs)
        return jax.tree.map(_stand_in, out)

    def _stand_in(s):
        """Zeros of an output's shape; a shape alone where the zeros would
        take more host memory than is sensible (a deployment-size slab)."""
        if np.prod(s.shape) * s.dtype.itemsize > LARGE:
            return jax.ShapeDtypeStruct(s.shape, s.dtype)
        return jnp.zeros(s.shape, s.dtype)

    def _pspecs():
        from repro.engine.sharded import batch_pspec, bank_pspec, slab_pspec
        from jax.sharding import PartitionSpec as P

        return {"bank": bank_pspec(), "slab": slab_pspec(), "rows": bank_pspec(),
                "batch": batch_pspec(), "ids": batch_pspec(), "scalar": P()}

    engine_mod.SketchEngine._compiled = compiled
    new_slab = engine_mod.SketchEngine.new_slab

    def abstract_slab(self, num_nodes):
        """The ring slab as shapes only: it is never read on the host."""
        out = jax.eval_shape(lambda: new_slab(_Host(self), num_nodes))
        return jax.tree.map(_stand_in, out)

    engine_mod.SketchEngine.new_slab = abstract_slab
    if args.shards:
        from repro.engine.sharded import ShardedEngine

        # the sharded fleet rollups keep their own executable cache, outside
        # the intercepted path: they are not rehearsed here
        ShardedEngine.rollup_quantiles = lambda self, bank, qs: np.zeros(len(qs))
        ShardedEngine.window_rollup = lambda self, *a: np.zeros(len(a[-1]))
    spec, win = _window(harness, config, args.shards)
    bodies = harness.reference.Bodies()
    harness.allocate_keys(win, config, traffic, bodies)
    if config.get("num_slices"):
        win.record_batches([("/k00000", np.ones(1000, np.float32), None)])
        win.advance_slice()
    harness.warm(win, config, traffic, config["gateway"]["max_queue_values"])
    per_chip = max(r["argument_bytes"] + r["output_bytes"] - r["alias_bytes"]
                   + r["temp_bytes"] for r in records)
    print(json.dumps({"config": args.config, "capacity": config["capacity"],
                      "shards": args.shards or 1, "executables": len(records),
                      "all_have_kernels": all(r["kernels"] for r in records
                                              if r["key"][0] not in
                                              ("snapshot", "reset", "slab_seal")),
                      "max_executable_bytes_per_chip": per_chip}))
    return 0


LARGE = 512 << 20  # bytes of zeros the rehearsal makes on the host at most


class _Host:
    """An engine as ``new_slab`` sees it, minus the device placement."""

    def __init__(self, engine):
        self.spec, self.num_sketches = engine.spec, engine.num_sketches
        self.counts_dtype = engine.counts_dtype

    def _place_slab(self, slab):
        return slab


def _window(harness, config, shards):
    from repro.core.jax_sketch import BucketSpec
    from repro.telemetry.keyed import KeyedWindow

    spec = BucketSpec(relative_accuracy=config["relative_accuracy"],
                      num_buckets=config["num_buckets"], offset=config["bucket_offset"])
    win = KeyedWindow(spec, capacity=config["capacity"],
                      collapse_threshold=config["collapse_threshold"],
                      num_slices=config.get("num_slices"),
                      slice_seconds=config.get("slice_seconds"), num_shards=shards)
    return spec, harness.proxy.WindowProxy(win)


if __name__ == "__main__":
    sys.exit(main())
