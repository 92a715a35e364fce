#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``);
each metric is read by ``metrics/<name>.py``.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  The last line of standard output is the
result object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: every
number the correctness check compared, beside its limit); the compared
numbers are also the last lines of standard error.  Earlier lines carry
the set-up's details.  A run that finds no TPU, or fewer chips than the
cell asks for, exits non-zero and prints no result.

``--variant control`` runs the program with bfloat16 counts instead of
float32, the control of the correctness check; ``--set
<stream kind>.<field>=<number>`` changes one number of the traffic mix
(``sweep.py`` finds a rate with it).  Measured runs use neither.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

T_START = time.monotonic()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--variant", choices=("control",), default=None)
    ap.add_argument("--set", action="append", default=[], metavar="KIND.FIELD=NUMBER")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import tempfile

    import harness

    bench = harness.load_benchmark()
    cell, config, traffic = harness.cell_spec(bench, args.workload)
    for item in args.set:
        path, value = item.split("=")
        kind, field = path.split(".")
        for s in traffic["streams"]:
            if s["kind"] == kind:
                s[field] = float(value)
    with tempfile.TemporaryDirectory(prefix="ddsketch-bench-") as tmp:
        gens = harness.Generators(config, traffic, args.seed, Path(tmp))
        try:
            import jax

            devices = jax.devices()
            if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
                print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
                      f"JAX found {len(devices)} {devices[0].platform!r} device(s)",
                      file=sys.stderr)
                return 3
            out = harness.run_cell(
                bench, args.workload, args.seed, args.seconds, bool(args.trace),
                t_start=T_START, gens=gens, tmp=Path(tmp), variant=args.variant,
                specs=(cell, config, traffic),
                log=lambda line: print(line, flush=True))
        finally:
            gens.stop()
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
