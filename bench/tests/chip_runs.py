"""Run the benchmark's command once per seed, one process after another, as
the check does, and keep each run's result line and the end of its
standard error.

  python3 bench/tests/chip_runs.py <out_dir> <workload> <seconds> <trace> <seed>...
"""
import json
import subprocess
import sys
import time
from pathlib import Path

out, workload, seconds, trace = Path(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4]
out.mkdir(parents=True, exist_ok=True)
for seed in sys.argv[5:]:
    t0 = time.time()
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", workload, "--seed", seed,
                        "--seconds", seconds, "--trace", trace],
                       capture_output=True, text=True, timeout=1300)
    stem = f"{workload}_{seed}_t{trace}"
    (out / f"{stem}.err").write_text(p.stderr[-20000:])
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    (out / f"{stem}.json").write_text(last)
    try:
        res = json.loads(last)
        summary = {k: round(v["value"], 4) for k, v in res["metrics"].items()}
        print(json.dumps({"seed": seed, "trace": trace, "rc": p.returncode, "wall_s":
                          round(time.time() - t0, 1), "correct": res["correct"],
                          "metrics": summary,
                          "checks": {k: round(v["value"], 6) for k, v in res["checks"].items()}}),
              flush=True)
    except (ValueError, KeyError):
        print(json.dumps({"seed": seed, "rc": p.returncode, "wall_s": round(time.time() - t0, 1),
                          "stderr": p.stderr[-1500:]}), flush=True)
