"""Chip sweep of the online rate of a cell, to find the highest rate the
system sustains without a growing online queue (the knee).

  python3 bench/tests/chip_sweep.py <workload> <seconds> <seed> [--layers N]
      [--drain S] <rate>...

``--layers`` runs the cell's configuration at another depth (to find the
deepest that fits the chip) and ``--drain`` shortens the wait for the
online requests due in the window.  One process holds the chip for every
rate; each prints one JSON line, with the peak device memory so far.
"""
import argparse
import json
import time
from pathlib import Path
import sys

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import catalog  # noqa: E402
import harness  # noqa: E402

ap = argparse.ArgumentParser()
ap.add_argument("workload")
ap.add_argument("seconds", type=float)
ap.add_argument("seed", type=int)
ap.add_argument("rates", type=float, nargs="+")
ap.add_argument("--layers", type=int)
ap.add_argument("--drain", type=float)
args = ap.parse_args()

config, traffic = catalog.Catalog.config, catalog.Catalog.traffic
if args.layers:
    def with_layers(self, name):
        c = config(self, name)
        c["num_hidden_layers"] = args.layers
        return c

    catalog.Catalog.config = with_layers
for rate in args.rates:
    def with_rate(self, name, _rate=rate):
        t = traffic(self, name)
        t["online"]["rate_per_s"] = _rate
        if args.drain is not None:
            t["online"]["drain_s"] = args.drain
        return t

    catalog.Catalog.traffic = with_rate
    t0 = time.perf_counter()
    res = harness.run_cell(args.workload, args.seed, args.seconds, False, root=Path.cwd(),
                           t_start=t0)
    print(json.dumps({"rate_per_s": rate, "layers": args.layers, "correct": res["correct"],
                      "metrics": res["metrics"], "device": res["device"],
                      "checks": res["checks"]}), flush=True)
