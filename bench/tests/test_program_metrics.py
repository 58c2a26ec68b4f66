"""The readers of the program's own spans, counters and wall stamps, and of
the engine's named device programs: each on a hand-built window, then a
traced tiny CPU run, where the host readers report and the device readers
find no device plane."""
import sys
import time
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402
from catalog import Catalog  # noqa: E402

SEED = 2**31 + 91
HOST = ("runtime.fill_host_ms_per_iter", "scheduler.host_ms_per_quantum",
        "scheduler.online_ttft_p95_ms", "scheduler.online_queue_wait_p95_ms")
DEVICE = ("engine.decode_device_us_per_token", "engine.prefill_device_us_per_token")
MS = 1_000_000  # ns


def reader(name):
    return Catalog(tiny.REPO).reader(name)


def req(priority, arrival, admit, first):
    return types.SimpleNamespace(
        priority=types.SimpleNamespace(value=priority), arrival_wall_ns=arrival,
        admit_wall_ns=admit, first_token_wall_ns=first)


def window(**kw):
    w = types.SimpleNamespace(counters={}, iterations=4, spans=[], requests={},
                              trace=None, decoded=[])
    w.__dict__.update(kw)
    return w


def quantum(t0, t1):
    return {"type": "quantum", "args": {"k": 1, "wall_ns": [t0, t1]}}


def test_fill_host_ms_per_iter():
    w = window(counters={"host_ns/runtime.fill": 100 * MS, "host_ns/engine.fetch": 20 * MS})
    assert reader("runtime.fill_host_ms_per_iter").read(w) == pytest.approx(20.0)
    assert reader("runtime.fill_host_ms_per_iter").read(window()) is None  # no spans


def test_host_ms_per_quantum():
    w = window(counters={"host_ns/core.step": 30 * MS, "host_ns/engine.fetch": 10 * MS,
                         "core/quanta": 8})
    assert reader("scheduler.host_ms_per_quantum").read(w) == pytest.approx(2.5)
    w.counters["core/quanta"] = 0
    assert reader("scheduler.host_ms_per_quantum").read(w) is None


def test_online_ttft_and_queue_wait_on_wall_stamps():
    reqs = {i: req("online", i * MS, i * MS + 2 * MS, i * MS + (10 + i) * MS)
            for i in range(20)}
    reqs[100] = req("offline", 0, MS, 500 * MS)  # offline: left out
    reqs[101] = req("online", 50 * MS, 51 * MS, 999 * MS)  # after the last quantum
    reqs[102] = req("online", 5 * MS, None, None)  # never admitted
    w = window(requests=reqs, spans=[quantum(0, 10 * MS), {"type": "span"},
                                     quantum(11 * MS, 40 * MS)])
    ttft = reader("scheduler.online_ttft_p95_ms").read(w)
    assert ttft == pytest.approx(10 + 0.95 * 19)  # np.percentile of 10..29 ms
    assert reader("scheduler.online_queue_wait_p95_ms").read(w) == pytest.approx(2.0)
    parent = window(requests={i: types.SimpleNamespace(priority=r.priority)
                              for i, r in reqs.items()},
                    spans=[{"type": "quantum", "args": {"k": 1}}])
    assert reader("scheduler.online_ttft_p95_ms").read(parent) is None


def test_device_readers_take_their_programs():
    trace = {"program_s": {"jit_decode_loop": 0.3, "jit_prefill_chunks_into_slots": 0.1,
                           "jit_prefill_suffix_into_slot": 0.02, "jit_train_step": 5.0,
                           "jit__unknown": 9.0}}
    w = window(trace=trace, decoded=[100] * 1000,
               counters={"engine/prefill_metered_tokens": 600})
    assert reader("engine.decode_device_us_per_token").read(w) == pytest.approx(300.0)
    assert reader("engine.prefill_device_us_per_token").read(w) == pytest.approx(200.0)
    unnamed = window(trace={"program_s": {"jit__unknown": 9.0}}, decoded=[100],
                     counters={"engine/prefill_metered_tokens": 600})
    for name in DEVICE:
        assert reader(name).read(unnamed) is None
        assert reader(name).read(window()) is None


def test_traced_tiny_run_reports_the_host_readers_only(tmp_path):
    import harness

    root = tiny.make_root(tmp_path)
    res = harness.run_cell("tiny-cell", SEED, 2.0, True, root=root,
                           t_start=time.perf_counter(), require_tpu=False)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert set(HOST) <= set(got) and not set(DEVICE) & set(got)
    fill_host = got["runtime.fill_host_ms_per_iter"]["value"]
    assert 0 < fill_host < got["runtime.fill_ms_per_iter"]["value"]
    assert got["scheduler.host_ms_per_quantum"]["value"] > 0
    assert 0 <= got["scheduler.online_queue_wait_p95_ms"]["value"] \
        <= got["scheduler.online_ttft_p95_ms"]["value"]
