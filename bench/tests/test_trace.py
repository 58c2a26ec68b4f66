"""Trace reduction: busy/idle union, per-kernel and per-program sums, idle
gaps labelled by the host span they fall in."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import devtrace as tr  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"
DATA = Path(__file__).resolve().parent / "data"


def ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


def hand_built():
    """Operations named by their HLO text, as the chip's trace names them."""
    return [
        ev(HOST, "python3", "bench.window", 0, 1000),
        ev(HOST, "python3", "bench.run", 0, 1000),
        ev(HOST, "python3", "bench.train_step", 0, 400),
        ev(DEV, tr.MODULES, "jit_train_step(7)", 10, 380),
        ev(DEV, tr.OPS, "%fusion.1 = f32[8] fusion(f32[8] %p)", 10, 200),
        ev(DEV, tr.OPS, "%fusion.2 = f32[8] fusion(f32[8] %fusion.1)", 150, 240),  # overlaps
        ev(DEV, tr.MODULES, "jit__unknown(9)", 500, 300),
        ev(DEV, tr.OPS, "%paged_decode_attention.3 = bf16[16] custom-call(s32[16] %a)", 500, 100),
        # consumes the kernel's output: named after it, not part of it
        ev(DEV, tr.OPS, "%slice.39 = bf16[8] slice(bf16[16] %paged_decode_attention.3)", 600, 1),
        ev(DEV, tr.OPS, "%paged_decode_attention.4 = bf16[16] custom-call(s32[16] %a)", 700, 100),
        ev(DEV, tr.OPS, "%outside.1 = f32[1] add(f32[1] %x)", 1100, 50),  # past the window
    ]


def test_union_merges_overlaps():
    assert tr.union([(0, 5), (3, 8), (10, 12), (12, 13)]) == [[0, 8], [10, 13]]


def test_busy_and_gaps_on_hand_built_events():
    events = hand_built()
    assert tr.busy_ns(events, DEV, 0, 1000) == 380 + 101 + 100
    assert tr.gaps(events, DEV, 0, 1000) == [(0, 10), (390, 500), (601, 700), (800, 1000)]


def test_summary_sums_kernels_programs_and_labels_gaps():
    s = tr.summarize(hand_built())
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(581e-9)
    assert tr.seconds(s["op_s"], "paged_decode_attention") == pytest.approx(200e-9)
    assert tr.seconds(s["program_s"], "jit_train_step") == pytest.approx(380e-9)
    assert sum(s["program_s"].values()) == pytest.approx(680e-9)
    idle = dict(s["idle_gaps"])
    assert idle["train_step"] == pytest.approx(10e-9)  # 0..10 inside the train step span
    assert idle["fill"] == pytest.approx(409e-9)  # the rest, inside bench.run
    ops = dict(s["device_ops"])
    assert ops["paged_decode_attention.3"] == pytest.approx(100e-9)
    assert ops["slice.39"] == pytest.approx(1e-9)


def test_no_window_reads_nothing():
    events = [e for e in hand_built() if e[2] != "bench.window"]
    assert tr.summarize(events) is None


def test_recorded_chip_trace():
    """60 ms of the offline cell's window on a TPU v5e."""
    rec = json.loads((DATA / "chip_trace.json").read_text())
    events = [tuple(e) for e in rec["events"]]
    s = tr.summarize(events)
    for key, want in rec["expect"].items():  # a sweep-line count, not the merge
        got = tr.seconds(s["op_s"], rec["kernels"][key[len("kernel:"):]]) \
            if key.startswith("kernel:") else s[key]
        assert got == pytest.approx(want, rel=1e-9, abs=1e-12), key
    assert 0 < s["busy_s"] <= s["window_s"]
