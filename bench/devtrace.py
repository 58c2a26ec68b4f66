"""Reduction of a profiler trace to device busy time, program and kernel
times, and idle gaps labelled by what the host was doing.

``read_xspace`` flattens a ``.xplane.pb`` into plain event tuples; every
other function works on those tuples, so the tests feed it hand-built
lists and a small recorded chip trace.  An event is
``(plane, line, name, start_ns, dur_ns)``.  Device planes are named
``/device:<KIND>:<n>``; their ``XLA Ops`` line holds the operations and
their ``XLA Modules`` line the compiled programs.  The host spans are the
benchmark's own ``TraceAnnotation`` names, all starting with ``bench.``.
"""
from __future__ import annotations

import collections
import glob
import os
import re

OPS, MODULES = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."


def read_xspace(trace_dir: str) -> list:
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for path in paths:
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            device = is_device(plane.name)
            for line in plane.lines:
                if device and line.name not in (OPS, MODULES):
                    continue
                for ev in line.events:
                    if not device and not ev.name.startswith(SPAN_PREFIX):
                        continue
                    out.append((plane.name, line.name, ev.name, float(ev.start_ns),
                                float(ev.duration_ns)))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def device_planes(events) -> list:
    return sorted({e[0] for e in events if is_device(e[0])})


def union(intervals) -> list:
    """Merge [start, end) intervals into disjoint ones, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(events, plane, lo, hi) -> float:
    ivs = clip([(e[3], e[3] + e[4]) for e in events if e[0] == plane and e[1] == OPS], lo, hi)
    return sum(e - s for s, e in union(ivs))


def gaps(events, plane, lo, hi) -> list:
    """Idle [start, end) intervals of one device inside the window."""
    ivs = union(clip([(e[3], e[3] + e[4]) for e in events if e[0] == plane and e[1] == OPS],
                     lo, hi))
    out, at = [], lo
    for s, e in ivs:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def window(events, name: str = SPAN_PREFIX + "window"):
    ws = [(e[3], e[3] + e[4]) for e in events if e[2] == name and not is_device(e[0])]
    if not ws:
        return None
    return min(s for s, _ in ws), max(e for _, e in ws)


def label(t: float, spans: list) -> str:
    """What the host was doing at time t: the innermost benchmark span
    holding t, named for the layer it covers."""
    inner = None
    for s, e, name in spans:
        if s <= t < e and (inner is None or e - s < inner[1] - inner[0]):
            inner = (s, e, name)
    if inner is None:
        return "harness"
    return {"bench.train_step": "train_step", "bench.run": "fill"}.get(inner[2], "harness")


def op_id(name: str) -> str:
    """An operation's own name: the trace names an operation by its whole
    HLO text, ``%fusion.235 = (f32[...]) fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def base(name: str) -> str:
    """A program name without its instance suffix (``jit_f(12)``)."""
    return re.sub(r"\(\d+\)$", "", name)


def seconds(table: dict, prefixes) -> float:
    """Seconds of the entries of a per-operation or per-program table whose
    names start with one of ``prefixes``."""
    prefixes = (prefixes,) if isinstance(prefixes, str) else tuple(prefixes)
    return sum(v for k, v in table.items() if k.startswith(prefixes))


def summarize(events) -> dict:
    """Per-window device summary, averaged over the device planes: busy
    time, the seconds of every operation (``op_s``, by its own name) and of
    every compiled program (``program_s``), and the idle gaps labelled by
    the host span they fell in.  A per-layer reader picks its kernel or
    program out of ``op_s`` / ``program_s`` by name."""
    win = window(events)
    planes = device_planes(events)
    if win is None or not planes:
        return None
    lo, hi = win
    n = len(planes)
    busy = sum(busy_ns(events, p, lo, hi) for p in planes) / n
    ops = collections.Counter()
    mods = collections.Counter()
    for e in events:
        if e[0] not in planes:
            continue
        s, t = max(e[3], lo), min(e[3] + e[4], hi)
        if t <= s:
            continue
        if e[1] == OPS:
            ops[op_id(e[2])] += t - s
        else:
            mods[base(e[2])] += t - s
    spans = sorted((e[3], e[3] + e[4], e[2]) for e in events
                   if not is_device(e[0]) and e[2].startswith(SPAN_PREFIX)
                   and e[2] != SPAN_PREFIX + "window")
    idle_by = collections.Counter()
    longest = []
    for p in planes:
        for s, t in gaps(events, p, lo, hi):
            who = label((s + t) / 2, spans)
            idle_by[who] += (t - s) / n
            longest.append((t - s, who))
    longest.sort(reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "chips": n,
        "op_s": {k: v / n * 1e-9 for k, v in ops.items()},
        "program_s": {k: v / n * 1e-9 for k, v in mods.items()},
        "programs": [[k, v / n * 1e-9] for k, v in mods.most_common(10)],
        "device_ops": [[k, v / n * 1e-9] for k, v in ops.most_common(10)],
        "idle_gaps": ([[k, v * 1e-9] for k, v in idle_by.most_common()]
                      + [[f"{who}.gap{i}", d * 1e-9]
                         for i, (d, who) in enumerate(longest[: 10 - len(idle_by)])]),
    }
