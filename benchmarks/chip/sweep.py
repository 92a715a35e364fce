#!/usr/bin/env python3
"""Find a traffic mix's knee once, by a sweep of one open-loop rate.

    python3 benchmarks/chip/sweep.py --workload svc2048.dashboard \\
        --stream reads_open --field requests_per_s --rates 120,200,280 \\
        --seconds 10 --seed 7

Runs the cell once per rate, each in a process of its own (``run.py
--set``: one chip owner at a time, and nothing a run leaves on the device
reaches the next), with the named stream's ``field`` set to the rate, and
prints one JSON line per rate with the cell's end-to-end metrics,
``correct``, and the clients' latency and lateness
(``lateness_growth_ms``: how much later the last quarter of the window was
sent than the first; a rate the server does not hold makes it grow).  The
knee is the highest rate whose lateness does not grow; the traffic file
then states a fixed rate below it.  Measured runs never use this; it is
how the rates in ``traffic/`` were found.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--stream", required=True)
    ap.add_argument("--field", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    failed = 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        proc = subprocess.run(
            [sys.executable, str(HERE / "run.py"), "--workload", args.workload,
             "--seed", str(args.seed + i), "--seconds", str(args.seconds), "--trace", "0",
             "--set", f"{args.stream}.{args.field}={rate}"],
            capture_output=True, text=True)
        lines = proc.stdout.strip().splitlines()
        if proc.returncode != 0 or len(lines) < 2:
            failed += 1
            print(json.dumps({"rate": rate, "returncode": proc.returncode,
                              "stderr": proc.stderr[-800:]}), flush=True)
            continue
        out, detail = json.loads(lines[-1]), json.loads(lines[-2])
        print(json.dumps({"rate": rate, "correct": out["correct"],
                          "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                          "clients": detail["clients"]}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
