"""Chip probe: one run of a cell with the profiler on, the control readings
of the check, and a dump of the trace's planes, lines and operation names.

  python3 bench/tests/chip_probe.py <workload> <seed> <seconds> <out_dir>
"""
import collections
import json
import sys
import time

T0 = time.perf_counter()
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import harness  # noqa: E402
import devtrace as tr  # noqa: E402

workload, seed, seconds, out = sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), Path(sys.argv[4])
out.mkdir(parents=True, exist_ok=True)
raw = out / f"trace_{workload}_{seed}.json"
res = harness.run_cell(workload, seed, seconds, True, root=Path.cwd(), t_start=T0,
                       controls=True, keep_trace=str(raw))
events = json.loads(raw.read_text())
names = collections.Counter()
for p, line, name, s, d in events:
    names[(p, line, tr.base(name))] += d
top = [[p, line, n, v * 1e-9] for (p, line, n), v in names.most_common(300)]
(out / f"names_{workload}_{seed}.json").write_text(json.dumps(top, indent=0))
# keep a small slice of the raw trace (0.3 s of the window) for the tests
win = tr.window(events)
if win:
    lo = win[0] + 2e9
    small = [e for e in events if e[3] + e[4] > lo and e[3] < lo + 3e8
             or e[2] == tr.SPAN_PREFIX + "window"]
    (out / f"small_{workload}_{seed}.json").write_text(json.dumps(small))
raw.unlink()
print(json.dumps(res))
