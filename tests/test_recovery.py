"""Crash-safe serving (DESIGN.md §11): write-ahead journal durability,
deterministic replay recovery, group-commit loss bounds, restamped
deadline ages, fault-counter decay, torn-checkpoint fallback, and the
warm-state snapshot round trip.

The crash model throughout is ``RequestJournal.crash()``: the process
dies, everything after the last fsync is lost (Python's userspace buffer
AND the OS page cache are both volatile), and a fresh engine replays the
surviving journal prefix."""
import os

import jax
import numpy as np
import pytest

from repro import configs
from repro.checkpoint.checkpointer import Checkpointer
from repro.models import transformer as T
from repro.obs import Observability
from repro.obs.schema import validate_events
from repro.resilience import (
    EngineSnapshot,
    FaultInjector,
    FaultSpec,
    ProcessKilled,
    RequestJournal,
    read_journal,
)
from repro.serving.core import Grant, Priority, RequestState, SamplingParams
from repro.serving.engine import InferenceEngine

CFG = configs.smoke_config("qwen3-1.7b")
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
STEP_S = 0.002


def _engine(vnow, paged=True, start=0.0, **kw):
    vnow[0] = start
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 128)
    kw.setdefault("kv_page_size", None if paged else 0)
    kw.setdefault("obs", Observability(tracing=True))
    return InferenceEngine(CFG, PARAMS, clock=lambda: vnow[0], **kw)


def _step(core, vnow, token_budget=16):
    base = vnow[0]
    out = core.step(Grant(
        now=base, token_budget=token_budget,
        advance_clock=lambda steps, b=base: vnow.__setitem__(
            0, b + steps * STEP_S
        ),
    ))
    if out.cost_steps == 0 and not out.admitted:
        vnow[0] += STEP_S
    return out


def _drain(core, vnow, limit=400, token_budget=16):
    n = 0
    while core.has_unfinished:
        _step(core, vnow, token_budget=token_budget)
        n += 1
        assert n < limit, "core.step() made no progress"


def _submit(core, n_offline=2, n_online=3):
    rng = np.random.default_rng(0)
    reqs = [
        core.submit(
            rng.integers(0, CFG.vocab_size, 8),
            SamplingParams(max_new_tokens=12),
            priority=Priority.OFFLINE, arrival_time=0.0,
        )
        for _ in range(n_offline)
    ]
    for t in np.cumsum(rng.exponential(0.01, n_online)):
        reqs.append(core.submit(
            rng.integers(0, CFG.vocab_size, 8),
            SamplingParams(max_new_tokens=4, deadline_s=5.0),
            priority=Priority.ONLINE, arrival_time=float(t),
        ))
    return reqs


def _journal_streams(path):
    """(tokens, finish-records) per request id from the durable prefix."""
    records, _ = read_journal(path)
    toks, fins = {}, {}
    for rec in records:
        if rec["k"] == "delta":
            cur = toks.setdefault(rec["rid"], [])
            if rec["tot"] == len(cur) + len(rec["tok"]):
                cur.extend(rec["tok"])
        elif rec["k"] == "fin":
            fins.setdefault(rec["rid"], []).append(rec)
    return toks, fins


# ---------------------------------------------------------------------------
# Crash -> replay -> drain: exactly-once, byte-identical
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def journaled_pair(tmp_path_factory):
    """The same mixed online/offline workload drained twice on the virtual
    clock, with a journal attached and without one."""
    def run(path):
        vnow = [0.0]
        core = _engine(vnow).core
        journal = None
        if path is not None:
            journal = RequestJournal(path, fsync_interval=8)
            journal.attach(core)
        reqs = _submit(core, n_offline=2, n_online=8)
        _drain(core, vnow)
        if journal is not None:
            journal.close()
        return {
            "output_tokens": [list(r.output_tokens) for r in reqs],
            "finished": [r.finish_reason for r in reqs],
            "virtual_time_s": vnow[0],
            "appends": core.obs.metrics.counter("journal/appends").value,
        }

    path = str(tmp_path_factory.mktemp("journal") / "j.jsonl")
    return run(path), run(None)


@pytest.mark.parametrize("row", ["output_tokens", "finished",
                                 "virtual_time_s"])
def test_journal_leaves_the_schedule_unchanged(journaled_pair, row):
    """Journal I/O is host-side, between quanta: the journaled run emits
    the same tokens, finishes the same requests the same way and ends at
    the same virtual time, exactly — and it did write the journal."""
    journaled, plain = journaled_pair
    assert journaled["appends"] >= 1 and plain["appends"] == 0
    assert journaled[row] == plain[row]


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_crash_recover_byte_identical(tmp_path, paged):
    """Kill mid-run, replay the journal into a FRESH engine, drain: every
    request finishes exactly once with the same bytes as an uninterrupted
    run — verified from the journal, the only cross-incarnation record."""
    vnow = [0.0]
    ref_core = _engine(vnow, paged=paged).core
    ref = _submit(ref_core)
    _drain(ref_core, vnow)

    path = str(tmp_path / "j.jsonl")
    vnow = [0.0]
    core = _engine(vnow, paged=paged).core
    journal = RequestJournal(path, fsync_interval=4)
    journal.attach(core)
    rid0 = _submit(core)[0].request_id
    for _ in range(5):
        _step(core, vnow)
    assert core.has_unfinished  # the crash interrupts real work
    journal.crash()

    vnow2 = [0.0]
    core2 = _engine(vnow2, paged=paged).core
    journal2 = RequestJournal(path, fsync_interval=4)
    report = journal2.recover_into(core2)
    journal2.attach(core2)
    assert report.restored + report.skipped_finished == len(ref)
    _drain(core2, vnow2)
    journal2.close()

    toks, fins = _journal_streams(path)
    for i, r in enumerate(ref):
        rid = rid0 + i
        assert fins.get(rid) is not None and len(fins[rid]) == 1, (
            f"request {rid} must reach a terminal state exactly once"
        )
        assert fins[rid][0]["rsn"] == r.finish_reason
        if r.finish_reason in ("stop", "length"):
            assert toks.get(rid, []) == list(r.output_tokens), (
                f"request {rid} recovered stream diverged"
            )


def test_kill_during_prefilling(tmp_path):
    """A long prompt mid-chunked-prefill at the crash re-enters as
    PREEMPTED and re-prefills to a byte-identical stream."""
    prompt = np.arange(96) % CFG.vocab_size
    sp = SamplingParams(max_new_tokens=6)

    vnow = [0.0]
    ref_core = _engine(vnow).core
    ref = ref_core.submit(prompt, sp, arrival_time=0.0)
    _drain(ref_core, vnow, token_budget=16)

    path = str(tmp_path / "j.jsonl")
    vnow = [0.0]
    core = _engine(vnow).core
    journal = RequestJournal(path, fsync_interval=1)
    journal.attach(core)
    r = core.submit(prompt, sp, arrival_time=0.0)
    _step(core, vnow, token_budget=16)  # 96-token prompt >> 16-token grant
    assert r.state is RequestState.PREFILLING
    journal.crash()

    vnow2 = [0.0]
    core2 = _engine(vnow2).core
    journal2 = RequestJournal(path, fsync_interval=1)
    report = journal2.recover_into(core2)
    journal2.attach(core2)
    assert report.resumed_inflight == 1
    cr = core2.requests[r.request_id]
    assert cr.state is RequestState.PREEMPTED
    _drain(core2, vnow2, token_budget=16)
    assert cr.finish_reason == ref.finish_reason
    assert list(cr.output_tokens) == list(ref.output_tokens)


def test_retry_at_survives_restore(tmp_path):
    """A quarantined request's backoff gate and fault count carry across
    the crash (shifted onto the restored clock) — a restart must not
    reset a request's retry budget or let it jump its backoff."""
    path = str(tmp_path / "j.jsonl")
    inj = FaultInjector(seed=3, specs=(
        FaultSpec("engine/nan_logits", probability=1.0, max_fires=1),
    ))
    vnow = [0.0]
    core = _engine(vnow, fault_injector=inj).core
    core.fault_backoff_s = 50.0  # backoff far beyond the drain horizon
    journal = RequestJournal(path, fsync_interval=1)
    journal.attach(core)
    r = core.submit(np.arange(6), SamplingParams(max_new_tokens=8),
                    arrival_time=0.0)
    for _ in range(6):
        _step(core, vnow)
    assert inj.total_fires == 1
    assert r.faults == 1 and r.retry_at > vnow[0]
    pre_crash_gap = r.retry_at - vnow[0]
    journal.crash()

    vnow2 = [100.0]
    core2 = _engine(vnow2, start=100.0).core
    journal2 = RequestJournal(path, fsync_interval=1)
    report = journal2.recover_into(core2)
    assert report.resumed_inflight == 1
    cr = core2.requests[r.request_id]
    assert cr.faults == 1
    # shifted, not reset: the remaining backoff is preserved on the new
    # clock (the journal stamped retry_at after the last delta, so the
    # surviving gap can only be >= what the dead process last observed)
    assert cr.retry_at - 100.0 >= pre_crash_gap - 1e-9
    assert cr.retry_at > 100.0


def test_group_commit_loss_window(tmp_path):
    """A crash loses AT MOST the configured group-commit interval of
    records — asserted, not assumed."""
    path = str(tmp_path / "j.jsonl")
    interval = 8
    journal = RequestJournal(path, fsync_interval=interval)
    durable_before = len(read_journal(path)[0])  # meta is fsync'd eagerly
    for i in range(interval - 3):
        journal._append({"k": "tr", "rid": i, "t": 0.0, "st": "waiting",
                         "f": 0, "ra": 0.0})
    pending = journal.pending_records
    assert 0 < pending < interval
    journal.crash()
    records, torn = read_journal(path)
    assert torn == 0
    lost = durable_before + (interval - 3) - len(records)
    assert lost == pending
    assert lost <= interval


def test_fsync_interval_bounds_pending(tmp_path):
    """The group-commit policy fsyncs automatically every N appends, so
    the loss window can never exceed N."""
    journal = RequestJournal(str(tmp_path / "j.jsonl"), fsync_interval=4)
    for i in range(23):
        journal._append({"k": "tr", "rid": i, "t": 0.0, "st": "waiting",
                         "f": 0, "ra": 0.0})
        assert journal.pending_records < 4
    journal.close()


def test_double_restore_idempotent(tmp_path):
    """Replaying the same journal into a core that already holds the
    requests restores nothing new (skipped_present), and replay never
    duplicates a durably-finished request."""
    path = str(tmp_path / "j.jsonl")
    vnow = [0.0]
    core = _engine(vnow).core
    journal = RequestJournal(path, fsync_interval=1)
    journal.attach(core)
    _submit(core)
    for _ in range(4):
        _step(core, vnow)
    journal.crash()

    vnow2 = [0.0]
    core2 = _engine(vnow2).core
    journal2 = RequestJournal(path, fsync_interval=1)
    first = journal2.recover_into(core2)
    assert first.restored > 0
    again = journal2.recover_into(core2)
    assert again.restored == 0
    assert again.skipped_present == first.restored
    assert again.skipped_finished == first.skipped_finished
    # queues were not double-populated
    depth = sum(len(q) for q in core2.waiting.values())
    assert depth == first.restored


def test_deadline_ages_not_reset(tmp_path):
    """Restamping preserves each request's CONSUMED deadline age: after a
    restart far in the future no queue mass-expires (ages carry over,
    budgets don't vanish) and ages don't silently reset either."""
    path = str(tmp_path / "j.jsonl")
    vnow = [0.0]
    core = _engine(vnow).core
    journal = RequestJournal(path, fsync_interval=1)
    journal.attach(core)
    reqs = _submit(core)  # online requests carry deadline_s=5.0
    for _ in range(3):
        _step(core, vnow)
    aged = vnow[0]
    journal.crash()

    # the new process comes up with a clock far past every old deadline
    vnow2 = [1000.0]
    core2 = _engine(vnow2, start=1000.0).core
    journal2 = RequestJournal(path, fsync_interval=1)
    report = journal2.recover_into(core2)
    journal2.attach(core2)
    assert report.restored > 0
    for rid, cr in core2.requests.items():
        old = next(r for r in reqs if r.request_id == rid)
        age_before = aged - old.arrival_time
        age_after = vnow2[0] - cr.arrival_time
        assert age_after == pytest.approx(age_before, abs=1e-9)
    _drain(core2, vnow2)
    m = core2.obs.metrics
    assert m.counter("core/finish_reason/expired").value == 0, (
        "restored requests mass-expired — deadline budgets were not "
        "restamped onto the restored clock"
    )


def test_recovery_trace_schema_and_attribution(tmp_path):
    """The recovery span and arrival_restamp instants validate against
    the pinned schema, and SLO attribution still telescopes after replay
    (restamped arrivals may be negative — that is schema-legal)."""
    path = str(tmp_path / "j.jsonl")
    vnow = [0.0]
    core = _engine(vnow).core
    journal = RequestJournal(path, fsync_interval=1)
    journal.attach(core)
    _submit(core)
    for _ in range(4):
        _step(core, vnow)
    journal.crash()

    vnow2 = [0.0]
    core2 = _engine(vnow2).core
    journal2 = RequestJournal(path, fsync_interval=1)
    report = journal2.recover_into(core2)
    journal2.attach(core2)
    assert report.restored > 0
    _drain(core2, vnow2)
    tr = core2.obs.tracer
    events = [ev for ev in tr.events]
    spans = [ev for ev in events
             if ev.get("type") == "span" and ev.get("name") == "recovery"]
    restamps = [ev for ev in events
                if ev.get("type") == "instant"
                and ev.get("name") == "arrival_restamp"]
    assert len(spans) == 1
    assert spans[0]["args"]["requests"] == report.restored
    assert len(restamps) == report.restored
    assert validate_events(events) == []
    att = tr.attribution()
    for ra in att.values():
        if ra.finish_time is not None:
            assert abs(
                ra.total - (ra.finish_time - ra.arrival_time)
            ) < 1e-6


def test_runtime_rearms_bubble_filling_from_journal(tmp_path):
    """A restarted ``SpecInFRuntime`` given the dead incarnation's journal
    replays it before fresh submissions and serves the survivors inside
    training bubbles."""
    import itertools

    from repro.configs.base import SpecInFConfig
    from repro.core import SpecInFRuntime
    from repro.core.profiles import dp_profile

    path = str(tmp_path / "j.jsonl")
    vnow = [0.0]
    core = _engine(vnow).core
    journal = RequestJournal(path, fsync_interval=1)
    journal.attach(core)
    _submit(core)
    for _ in range(3):
        _step(core, vnow)
    journal.crash()

    vnow2 = [0.0]
    engine2 = _engine(vnow2)
    journal2 = RequestJournal(path, fsync_interval=1)
    rt = SpecInFRuntime(
        train_step=lambda state, batch: (state, {"loss": 0.0}),
        train_state={}, batch_iter=itertools.repeat({}),
        profile=dp_profile("tiny", compute_s=0.03, comm_s=0.04),
        engine=engine2, cfg=SpecInFConfig(), decode_microstep_s=0.002,
        journal=journal2,
    )
    assert rt.recovery is not None and rt.recovery.restored > 0
    assert rt.core.journal is journal2  # this incarnation journals in turn
    rt.run(num_iterations=10)
    finished = sum(
        1 for cr in rt.core.requests.values() if cr.state.finished
    )
    m = engine2.obs.metrics
    assert m.counter("recovery/restores").value == 1
    assert finished > 0, (
        "bubble filling never finished a journal-restored request"
    )


def test_process_kill_fault_point():
    """The injected process death raises OUT of step() (nothing absorbs
    it) and is armed like any other seeded fault point."""
    vnow = [0.0]
    inj = FaultInjector(seed=1, specs=(
        FaultSpec("process/kill", probability=1.0, max_fires=1),
    ))
    core = _engine(vnow, fault_injector=inj).core
    core.submit(np.arange(6), SamplingParams(max_new_tokens=4),
                arrival_time=0.0)
    with pytest.raises(ProcessKilled):
        for _ in range(10):
            _step(core, vnow)
    assert inj.total_fires == 1


# ---------------------------------------------------------------------------
# Satellite: fault-counter decay (serving fairness)
# ---------------------------------------------------------------------------


def test_fault_decay_earns_retry_budget_back():
    """A long-lived request whose retry budget is already spent must earn
    it back after ``fault_decay_quanta`` consecutive clean quanta, so ONE
    more transient fault late in its life quarantines-and-retries instead
    of escalating to FINISHED_ERROR.  The control run (decay disabled)
    shows the old lifetime-counter unfairness: the same single late fault
    kills the request."""
    def run(decay_quanta):
        vnow = [0.0]
        # one late fault, long after the request has decoded cleanly
        # (token_budget=2 -> one fused dispatch == one consultation per
        # quantum, so ``after`` spaces the fault in clean-quantum units)
        inj = FaultInjector(seed=7, specs=(
            FaultSpec("engine/nan_logits", probability=1.0, after=12,
                      max_fires=1),
        ))
        core = _engine(vnow, fault_injector=inj, max_slots=1).core
        core.fault_backoff_s = 0.0
        core.fault_decay_quanta = decay_quanta
        r = core.submit(np.arange(6), SamplingParams(max_new_tokens=48),
                        arrival_time=0.0)
        r.faults = core.max_fault_retries  # budget spent early in life
        _drain(core, vnow, limit=800, token_budget=2)
        return r, core, inj

    r, core, inj = run(8)
    assert inj.total_fires == 1
    assert r.state is not RequestState.FINISHED_ERROR, (
        "a late transient fault escalated to FINISHED_ERROR despite the "
        "clean-quanta decay"
    )
    assert core.obs.metrics.counter("fault/decays").value >= 1
    r0, core0, inj0 = run(0)  # decay disabled: lifetime counter is unfair
    assert inj0.total_fires == 1
    assert r0.state is RequestState.FINISHED_ERROR
    assert core0.obs.metrics.counter("fault/decays").value == 0


# ---------------------------------------------------------------------------
# Satellite: torn-checkpoint fallback
# ---------------------------------------------------------------------------


def _state(val=1.0):
    return {"w": np.full((4, 4), val, np.float32)}


def test_checkpoint_restore_skips_torn_saves(tmp_path):
    """A crash mid-save leaves a torn step directory; restore must fall
    back to the newest VALID checkpoint instead of failing (or worse,
    loading garbage)."""
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state(1.0))

    # torn variant A: step dir without a manifest (killed before rename
    # machinery finished) — already invisible to all_steps
    os.makedirs(tmp_path / "step_00000002")
    # torn variant B: manifest present but complete:false
    d3 = tmp_path / "step_00000003"
    os.makedirs(d3)
    (d3 / "manifest.json").write_text('{"step": 3, "complete": false}')
    # torn variant C: valid manifest, corrupt arrays file
    d4 = tmp_path / "step_00000004"
    os.makedirs(d4)
    np.savez(d4 / "arrays.npz", **{"0": np.zeros(1)})
    raw = (d4 / "arrays.npz").read_bytes()
    (d4 / "arrays.npz").write_bytes(raw[: len(raw) // 2])  # truncate
    (d4 / "manifest.json").write_text(
        '{"step": 4, "complete": true, "leaves": 1}'
    )

    restored, step = ck.restore(_state(0.0))
    assert step == 1
    np.testing.assert_allclose(restored["w"], 1.0)
    # explicit-step restore falls back below the torn step too
    restored, step = ck.restore(_state(0.0), step=4)
    assert step == 1


def test_checkpoint_save_fsyncs_files_and_dirs(tmp_path, monkeypatch):
    """The save path must fsync payload, manifest, and the directories —
    rename-into-place alone is not durable."""
    calls = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (calls.append(fd),
                                                 real_fsync(fd))[1])
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state(2.0))
    # arrays.npz + manifest + tmp dir + parent dir
    assert len(calls) >= 4
    restored, step = ck.restore(_state(0.0))
    assert step == 1
    np.testing.assert_allclose(restored["w"], 2.0)


def test_checkpoint_restore_all_torn_raises(tmp_path):
    ck = Checkpointer(str(tmp_path))
    d1 = tmp_path / "step_00000001"
    os.makedirs(d1)
    (d1 / "manifest.json").write_text(
        '{"step": 1, "complete": true, "leaves": 1}'
    )  # manifest OK, arrays.npz missing entirely
    with pytest.raises(FileNotFoundError):
        ck.restore(_state(0.0))


# ---------------------------------------------------------------------------
# Warm-state snapshot
# ---------------------------------------------------------------------------


def test_snapshot_round_trip_warms_prefix_cache(tmp_path):
    """Snapshot the radix cache, restore into a COLD engine: resubmitted
    prompts hit the warmed prefix pages (prefill skipped) and decode
    byte-identically."""
    prompt = np.arange(32) % CFG.vocab_size
    sp = SamplingParams(max_new_tokens=4)

    vnow = [0.0]
    engine = _engine(vnow, kv_page_size=8)
    core = engine.core
    ref = core.submit(prompt, sp, arrival_time=0.0)
    _drain(core, vnow)
    ck = Checkpointer(str(tmp_path / "snap"))
    snap = EngineSnapshot(engine, ck)
    assert snap.save() is True

    vnow2 = [0.0]
    engine2 = _engine(vnow2, kv_page_size=8)
    snap2 = EngineSnapshot(engine2, Checkpointer(str(tmp_path / "snap")))
    loaded = snap2.restore()
    assert loaded > 0
    core2 = engine2.core
    m0 = engine2.obs.metrics.counter("engine/prefill_skipped_tokens").value
    r2 = core2.submit(prompt, sp, arrival_time=0.0)
    _drain(core2, vnow2)
    skipped = (
        engine2.obs.metrics.counter("engine/prefill_skipped_tokens").value
        - m0
    )
    # every reusable page came from the warmed cache: 3 of 4 pages — the
    # final position is always recomputed to produce the first logits
    assert skipped == 24
    assert list(r2.output_tokens) == list(ref.output_tokens)


def test_snapshot_discarded_when_it_outran_the_journal(tmp_path):
    """A snapshot whose journal watermark exceeds the surviving journal
    length (its tail died in the crash) must be discarded — warm state
    stays a strict subset of journaled truth."""
    path = str(tmp_path / "j.jsonl")
    vnow = [0.0]
    engine = _engine(vnow, kv_page_size=8)
    core = engine.core
    journal = RequestJournal(path, fsync_interval=1)
    journal.attach(core)
    core.submit(np.arange(32) % CFG.vocab_size,
                SamplingParams(max_new_tokens=4), arrival_time=0.0)
    _drain(core, vnow)
    ck = Checkpointer(str(tmp_path / "snap"))
    assert EngineSnapshot(engine, ck, journal=journal).save() is True
    journal.close()
    # the crash erases journal bytes the snapshot's watermark counted on
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)

    vnow2 = [0.0]
    engine2 = _engine(vnow2, kv_page_size=8)
    journal2 = RequestJournal(path, fsync_interval=1)
    snap2 = EngineSnapshot(
        engine2, Checkpointer(str(tmp_path / "snap")), journal=journal2
    )
    assert snap2.restore() == 0
    m = engine2.obs.metrics
    assert m.counter("recovery/snapshot_discarded").value == 1
