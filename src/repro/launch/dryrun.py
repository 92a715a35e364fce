import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder devices.  Smoke tests
and benchmarks never import this module, so they keep seeing 1 device.

Per cell this script:
  1. builds the step (train / prefill / serve) with the arch's sharding
     profile against ShapeDtypeStructs (zero allocation),
  2. ``jit(...).lower(...).compile()`` under the production mesh,
  3. records memory_analysis (fits-in-HBM proof), cost_analysis (FLOPs /
     bytes for §Roofline), and the parsed collective schedule,
  4. writes experiments/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh single
  python -m repro.launch.dryrun --all --mesh multi       # 2-pod, 512 chips
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.shapes import SHAPES, shapes_for  # noqa: E402
from repro.launch import roofline  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import StepConfig, build_cell  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

# Per-arch step-config overrides (memory knobs tuned via memory_analysis;
# the perf-iteration log in EXPERIMENTS.md §Perf records the tuning).
ARCH_SCFG: dict[str, dict] = {
    # 51865-wide vocab can't TP-shard (odd), so CE chunks stay small; 8
    # unsharded heads make full-seq q-blocks large at 4k.
    "whisper-base": dict(q_block=512, ce_chunk=256),
    # fsdp-profile archs keep full heads per chip: bound the f32 logits tile
    "smollm-135m": dict(q_block=1024, ce_chunk=512),
    "qwen3-0.6b": dict(q_block=1024, ce_chunk=512),
    # few big chunks: 32k/1024 chunks x 8-layer cycles made the nested-scan
    # prefill compile pathological (>30 min); 2048-chunks compile in ~2 min
    "xlstm-1.3b": dict(ssm_chunk=2048),
    "jamba-v0.1-52b": dict(ssm_chunk=1024),
}


def _scfg_for(arch: str, shape_name: str) -> StepConfig:
    shape = SHAPES[shape_name]
    kw = dict(ssm_chunk=shape.ssm_chunk, q_block=shape.q_block)
    kw.update(ARCH_SCFG.get(arch, {}))
    return StepConfig(**kw)


def _compile_variant(arch, shape_name, mesh, cfg, scfg):
    t0 = time.time()
    fn, args, in_shardings, out_shardings, donate = build_cell(
        arch, shape_name, mesh, scfg=scfg, cfg=cfg
    )
    jit_kwargs = dict(in_shardings=in_shardings)
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    if donate:
        jit_kwargs["donate_argnums"] = donate
    with mesh:
        lowered = jax.jit(fn, **jit_kwargs).lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    colls = roofline.parse_collectives(compiled.as_text())
    return {
        "compile_s": time.time() - t0,
        "mem": mem,
        "flops": float(cost.get("flops", 0.0)),
        "hbm_bytes": float(cost.get("bytes accessed", 0.0)),
        "colls": colls,
    }


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    scfg: StepConfig | None = None,
    tag: str = "",
    verbose: bool = True,
    cfg_overrides: dict | None = None,
) -> dict:
    """Compile strategy (DESIGN.md §7):

    * decode cells — one full-depth unrolled compile: temps are tiny at
      S=1, and FLOPs/collectives come out exact.
    * train/prefill cells — (A) full depth with lax.scan over layer cycles
      for the memory proof (XLA-CPU's scheduler keeps every unrolled
      buffer live, so unrolled memory numbers are meaningless — measured,
      see EXPERIMENTS.md §Dry-run), plus (B, C) unrolled 1- and 2-cycle
      compiles whose exact per-cycle deltas extrapolate FLOPs / HBM bytes /
      collective wire bytes to full depth (cycles are identical subgraphs;
      scan-counted-once costs would otherwise undercount ~n_cycles x).
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    base_cfg = configs.get(arch)
    if cfg_overrides:
        base_cfg = base_cfg.replace(**cfg_overrides)
    shape = SHAPES[shape_name]
    if scfg is None:
        scfg = _scfg_for(arch, shape_name)
    t_start = time.time()

    if multi_pod:
        # multi-pod pass proves the 'pod' axis shards (memory + collective
        # schedule); roofline terms are single-pod only (§Roofline), so the
        # scan-undercounted cost numbers are recorded but not extrapolated.
        A = _compile_variant(
            arch, shape_name, mesh, base_cfg.replace(scan_layers=True), scfg
        )
        mem = A["mem"]
        flops, hbm_bytes = A["flops"], A["hbm_bytes"]
        wire_bytes = A["colls"].wire_bytes
        coll_ops, coll_raw = A["colls"].ops, A["colls"].raw_bytes
        variants = {
            "scan_full": {
                "flops": flops,
                "wire_bytes": wire_bytes,
                "compile_s": A["compile_s"],
                "note": "scan body counted once; see 16x16 record for terms",
            }
        }
    else:
        cycle = base_cfg.cycle_len
        A = _compile_variant(
            arch, shape_name, mesh, base_cfg.replace(scan_layers=True), scfg
        )
        B = _compile_variant(
            arch, shape_name, mesh, base_cfg.replace(n_layers=cycle), scfg
        )
        C = _compile_variant(
            arch, shape_name, mesh, base_cfg.replace(n_layers=2 * cycle), scfg
        )
        n_cycles = base_cfg.n_cycles

        def extrap(b, c):
            return b + (n_cycles - 1) * (c - b)
        mem = A["mem"]
        flops = extrap(B["flops"], C["flops"])
        hbm_bytes = extrap(B["hbm_bytes"], C["hbm_bytes"])
        wire_bytes = extrap(B["colls"].wire_bytes, C["colls"].wire_bytes)
        kinds = set(B["colls"].ops) | set(C["colls"].ops)
        coll_ops = {
            k: int(extrap(B["colls"].ops.get(k, 0), C["colls"].ops.get(k, 0)))
            for k in kinds
        }
        coll_raw = {
            k: extrap(B["colls"].raw_bytes.get(k, 0), C["colls"].raw_bytes.get(k, 0))
            for k in kinds
        }
        variants = {
            "scan_full": {
                "flops": A["flops"],
                "wire_bytes": A["colls"].wire_bytes,
                "compile_s": A["compile_s"],
            },
            "unrolled_1cycle": {"flops": B["flops"], "compile_s": B["compile_s"]},
            "unrolled_2cycle": {"flops": C["flops"], "compile_s": C["compile_s"]},
        }

    compile_s = time.time() - t_start
    # the dry run plans v5e pods (the production meshes), so it reads the
    # v5e row whatever backend compiled it
    terms = roofline.roofline_terms(
        flops, hbm_bytes, wire_bytes, hw=roofline.peaks("TPU v5 lite")
    )

    n_params = base_cfg.param_count()
    n_active = base_cfg.active_param_count()
    # MODEL_FLOPS: 6·N·D for train, 2·N·D for inference (fwd only); D =
    # tokens processed this step (decode: one token per sequence).
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * n_active * tokens
    model_flops_per_chip = model_flops / n_chips

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "tag": tag,
        "n_chips": int(n_chips),
        "compile_s": round(compile_s, 1),
        "params": n_params,
        "active_params": n_active,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_hbm_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "cost": {"flops": flops, "hbm_bytes": hbm_bytes},
        "collectives": {
            "ops": coll_ops,
            "raw_bytes": coll_raw,
            "wire_bytes": wire_bytes,
        },
        "variants": variants,
        "roofline": terms,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_frac": model_flops_per_chip / flops if flops else 0.0,
    }
    if verbose:
        hbm_gb = record["memory"]["peak_hbm_bytes"] / 2**30
        print(
            roofline.fmt_row(
                f"{arch} x {shape_name} [{record['mesh']}]{tag}",
                terms,
                extra=f"hbm={hbm_gb:5.2f}GiB useful={record['useful_flops_frac']*100:5.1f}% compile={compile_s:.0f}s",
            ),
            flush=True,
        )
    return record


def save_record(rec: dict) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"__{rec['tag']}" if rec["tag"] else ""
    path = os.path.join(
        OUT_DIR, f"{rec['arch']}__{rec['shape']}__{rec['mesh'].replace('x','_')}{tag}.json"
    )
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def record_path(arch: str, shape: str, mesh: str, tag: str = "") -> str:
    t = f"__{tag}" if tag else ""
    return os.path.join(
        OUT_DIR, f"{arch}__{shape}__{mesh.replace('x', '_')}{t}.json"
    )


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, choices=configs.ARCHS)
    p.add_argument("--shape", default=None, choices=list(SHAPES))
    p.add_argument("--mesh", default="single", choices=["single", "multi"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--tag", default="")
    p.add_argument("--skip-existing", action="store_true")
    args = p.parse_args()

    cells = []
    if args.all:
        for arch in configs.ARCHS:
            for shape in shapes_for(arch):
                cells.append((arch, shape))
        # cheap cells first so a long sweep yields results early
        order = {"decode_32k": 0, "long_500k": 1, "prefill_32k": 2, "train_4k": 3}
        cells.sort(key=lambda c: order.get(c[1], 9))
    else:
        if not args.arch or not args.shape:
            p.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    mesh_name = "2x16x16" if args.mesh == "multi" else "16x16"
    failures = []
    for arch, shape in cells:
        if args.skip_existing and os.path.exists(
            record_path(arch, shape, mesh_name, args.tag)
        ):
            print(f"skip (exists): {arch} x {shape}", flush=True)
            continue
        try:
            rec = run_cell(arch, shape, args.mesh == "multi", tag=args.tag)
            save_record(rec)
        except Exception:
            failures.append((arch, shape))
            print(f"FAILED {arch} x {shape}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} cells failed: {failures}")
        return 1
    print(f"\nall cells compiled OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
