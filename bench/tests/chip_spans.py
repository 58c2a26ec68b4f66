"""One traced run of a cell that keeps the program's own host spans
(``specinf.*``, which the harness's trace reader drops) beside the device
operations, and puts the device's idle time in the window down to them:
each stretch of an idle gap goes to the innermost program span open over
it (``-`` where none is).  Also each span's host time per iteration, in all
and as innermost span (self time).

  python3 bench/tests/chip_spans.py <workload> <seed> <seconds> <out_dir>

Prints the run's result line, then one JSON line of the attribution, also
written to ``<out_dir>/spans_<workload>_<seed>.json``.
"""
import collections
import glob
import json
import os
import sys
import time

T0 = time.perf_counter()
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import devtrace as tr  # noqa: E402
import harness  # noqa: E402

PROGRAM = "specinf."
kept = {"device": None, "host": []}
read_xspace = tr.read_xspace


def read_keeping_program_spans(trace_dir):
    import jax

    kept["device"] = events = read_xspace(trace_dir)
    for path in sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if tr.is_device(plane.name):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM):
                        kept["host"].append((line.name, ev.name[len(PROGRAM):],
                                             float(ev.start_ns), float(ev.duration_ns)))
    return events


def innermost(spans, lo, hi):
    """Pieces ``(start, end, name)`` of [lo, hi), each with the innermost of
    the properly nested ``spans`` open over it (None where none is)."""
    out, stack, t = [], [], lo

    def emit(end, name):
        nonlocal t
        if end > t:
            out.append((t, min(end, hi), name))
            t = end

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1], stack[-1][2])
            stack.pop()
        emit(s, stack[-1][2] if stack else None)
        stack.append((s, e, name))
    while stack:
        emit(stack[-1][1], stack[-1][2])
        stack.pop()
    emit(hi, None)
    return [p for p in out if p[0] < hi and p[1] > lo]


def overlap_by_name(gaps, pieces):
    """Seconds of ``gaps`` under each piece's name (both sorted, disjoint)."""
    acc = collections.Counter()
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(ge, pieces[k][1]) - max(gs, pieces[k][0])
            if ov > 0:
                acc[pieces[k][2] or "-"] += ov * 1e-9
            k += 1
    return acc


def main():
    workload, seed, seconds, out = (sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
                                    Path(sys.argv[4]))
    out.mkdir(parents=True, exist_ok=True)
    tr.read_xspace = read_keeping_program_spans
    res = harness.run_cell(workload, seed, seconds, True, root=Path.cwd(), t_start=T0)
    print(json.dumps(res), flush=True)
    events, host = kept["device"], kept["host"]
    lo, hi = tr.window(events)
    planes = tr.device_planes(events)
    line = collections.Counter(h[0] for h in host).most_common(1)[0][0] if host else None
    spans = [(s, s + d, name) for ln, name, s, d in host
             if ln == line and s < hi and s + d > lo]
    pieces = innermost(spans, lo, hi)
    idle = collections.Counter()
    for p in planes:
        for name, secs in overlap_by_name(tr.gaps(events, p, lo, hi), pieces).items():
            idle[name] += secs / len(planes)
    iters = sum(1 for _, _, name in spans if name == "runtime.train_step") or 1
    total, count, self_s = collections.Counter(), collections.Counter(), collections.Counter()
    for s, e, name in spans:
        total[name] += (min(e, hi) - max(s, lo)) * 1e-9
        count[name] += 1
    for s, e, name in pieces:
        self_s[name or "-"] += (e - s) * 1e-9
    window_s = (hi - lo) * 1e-9
    busy = sum(tr.busy_ns(events, p, lo, hi) for p in planes) / max(len(planes), 1) * 1e-9
    report = {
        "workload": workload, "seed": seed, "window_s": window_s, "busy_s": busy,
        "idle_s": window_s - busy, "iterations": iters,
        "idle_by_innermost_span_s": dict(idle.most_common()),
        "host_ms_per_iter": {k: v / iters * 1e3 for k, v in total.most_common()},
        "self_ms_per_iter": {k: v / iters * 1e3 for k, v in self_s.most_common()},
        "spans_per_iter": {k: v / iters for k, v in count.most_common()},
    }
    (out / f"spans_{workload}_{seed}.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
