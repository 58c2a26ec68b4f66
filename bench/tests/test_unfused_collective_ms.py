"""The reader of ``train_step.unfused_collective_ms`` on a synthetic
trace: the operations named as collectives count, every other operation
(fusions included) does not, and a window with none reads nothing."""
import importlib.util
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import devtrace as tr  # noqa: E402

HOST = "/host:CPU"
PLANES = [f"/device:TPU:{i}" for i in range(4)]


def reader():
    path = BENCH / "metrics" / "train_step.unfused_collective_ms.py"
    spec = importlib.util.spec_from_file_location("unfused_collective_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def op(plane, name, start, dur):
    """An operation as the chip's trace names it: its whole HLO text."""
    return (plane, tr.OPS, f"%{name} = bf16[8] op(bf16[8] %p)", float(start), float(dur))


def window(ops):
    events = [(HOST, "python3", "bench.window", 0.0, 1e6)] + ops
    return types.SimpleNamespace(trace=tr.summarize(events), train_steps=2)


def test_collectives_count_and_nothing_else():
    ops = []
    for p in PLANES:  # every chip runs the same step
        ops += [op(p, "all-gather.3", 0, 100), op(p, "async-collective-start.1", 100, 50),
                op(p, "async-collective-done.1", 400, 50), op(p, "all-reduce.54", 500, 200),
                op(p, "reduce-scatter.2", 700, 100), op(p, "collective-permute-start.1", 800, 25),
                op(p, "collective-permute-done.1", 825, 25), op(p, "all-to-all.1", 850, 50),
                # not collectives: compute, a gather, fusions named after neither
                op(p, "fusion.235", 1000, 5000), op(p, "gather.4", 6000, 300),
                op(p, "reduce_sum.7", 6300, 300), op(p, "scatter-add.1", 6600, 300),
                op(p, "dynamic-slice_bitcast_fusion.5", 6900, 300)]
    ms = reader().read(window(ops))
    # 600 ns of collectives a chip over 2 steps, in ms
    assert ms == pytest.approx(600e-9 / 2 * 1e3)


def test_no_collective_reads_none():
    ops = [op(p, "fusion.235", 0, 100) for p in PLANES]
    assert reader().read(window(ops)) is None
    assert reader().read(types.SimpleNamespace(trace=None, train_steps=2)) is None
