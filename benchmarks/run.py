"""Benchmark orchestrator — one section per paper table/figure plus the
roofline table.  Prints ``figure,case,policy,metric,value`` CSV.

  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run --only fig4a roofline
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", nargs="*", default=None,
                    help="figure prefixes to run (fig4a ... fig8, headline, "
                         "roofline, micro)")
    ap.add_argument("--results-dir", default="results/dryrun")
    ap.add_argument("--bench-json", default="BENCH_engine.json",
                    help="where to write the engine microstep rows as JSON "
                         "(perf trajectory for future PRs); '' disables")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    want = lambda name: args.only is None or any(
        name.startswith(o) for o in args.only
    )

    t0 = time.time()
    rows = []

    from benchmarks import paper_fidelity as PF

    for mode, fa, fb in (("dp", "fig4a", "fig4b"),
                         ("mp", "fig5a", "fig5b"),
                         ("pp", "fig6a", "fig6b")):
        if want(fa):
            rows += PF.bench_offline(mode)
        if want(fb):
            rows += PF.bench_online(mode)
    if want("fig7"):
        rows += PF.bench_multi_instance()
    if want("fig8"):
        rows += PF.bench_overhead()
    if want("headline"):
        rows += PF.bench_headline()
    if want("micro"):
        from benchmarks import engine_micro

        t_micro = time.time()
        micro_rows = engine_micro.all_rows()
        rows += micro_rows
        if args.bench_json:
            import json

            with open(args.bench_json, "w") as f:
                json.dump(
                    {
                        "schema": ["figure", "case", "policy", "metric", "value"],
                        "rows": [list(r) for r in micro_rows],
                        "elapsed_s": round(time.time() - t_micro, 2),
                    },
                    f,
                    indent=2,
                )
            print(f"# wrote {args.bench_json} ({len(micro_rows)} rows)",
                  file=sys.stderr)
    if want("roofline"):
        from benchmarks import roofline

        for mesh in ("single", "multi"):
            try:
                rows += roofline.table_rows(args.results_dir, mesh)
            except FileNotFoundError:
                print(f"# roofline/{mesh}: no dry-run artifacts, skipping",
                      file=sys.stderr)

    print("figure,case,policy,metric,value")
    for r in rows:
        print(",".join(str(x) for x in r))
    print(f"# {len(rows)} rows in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
