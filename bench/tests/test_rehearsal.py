"""CPU rehearsal at a tiny size: a whole run of the harness, the control,
and runs with the timed path broken underneath, which the check must call
not correct.  Nothing here is a device measurement."""
import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

SEED = 2**31 + 77


def run(root, seed=SEED, controls=False):
    import harness

    return harness.run_cell("tiny-cell", seed, 2.0, False, root=root,
                            t_start=time.perf_counter(), require_tpu=False, controls=controls)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def test_sound_run_is_correct_and_the_control_is_not(root):
    res = run(root, controls=True)
    assert res["correct"], res["checks"]
    assert list(res)[-2:] == ["checks", "readings"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "offline_tokens_per_s", "setup_s"}
    limits = {k: c["limit"] for k, c in res["checks"].items()}
    control = res["readings"]["control"]
    assert any(control[k] > limits[k] for k in limits), control


def test_traced_run_reads_host_metrics_and_no_device_metric(root):
    """On the CPU the trace holds no device plane: the readers of host
    spans and counters report, none of the device's metrics does."""
    import harness

    res = harness.run_cell("tiny-cell", SEED + 1, 2.0, True, root=root,
                           t_start=time.perf_counter(), require_tpu=False)
    assert res["correct"], res["checks"]
    got = set(res["metrics"])
    assert {"runtime.offline_tokens_per_iter", "runtime.fill_ms_per_iter",
            "scheduler.slot_occupancy", "runtime.online_ttft_p95_ms",
            "runtime.online_latency_p95_ms"} <= got
    assert not got & {"device.idle_share", "engine.device_us_per_token", "engine.mfu",
                      "train_step.mfu", "train_step.device_ms",
                      "paged_decode_attention_roofline", "paged_prefill_attention_roofline"}
    assert "busy_s" not in res["device"] and "breakdown" not in res
    assert 0 < res["metrics"]["scheduler.slot_occupancy"]["value"] <= 100


def test_state_left_unchanged_is_caught(root, monkeypatch):
    from repro.runtime.step import TrainStepArtifacts

    jitted = TrainStepArtifacts.jitted

    def frozen(self, donate=True):
        fn = jitted(self, donate)
        return lambda state, batch: (state, fn(state, batch)[1])

    monkeypatch.setattr(TrainStepArtifacts, "jitted", frozen)
    res = run(root)
    assert not res["correct"]
    assert res["checks"]["train.update_gap"]["value"] > res["checks"]["train.update_gap"]["limit"]


def test_half_batch_is_caught(root, monkeypatch):
    from repro.models import transformer as T

    lm_loss = T.lm_loss

    def half(cfg, params, inputs, labels, **kw):
        n = inputs.shape[0] // 2
        return lm_loss(cfg, params, inputs[:n], labels[:n], **kw)

    monkeypatch.setattr(T, "lm_loss", half)
    res = run(root)
    assert not res["correct"], res["checks"]


def test_altered_token_is_caught(root, monkeypatch):
    from repro.serving.engine import InferenceEngine

    drive = InferenceEngine._drive_decode_loop

    def altered(self, k):
        out = drive(self, k)
        for r in self.slots:
            if r is not None and r.generated:
                r.generated[-1] = (r.generated[-1] + 1) % self.cfg.vocab_size
                break
        return out

    monkeypatch.setattr(InferenceEngine, "_drive_decode_loop", altered)
    res = run(root)
    assert not res["correct"]
    assert res["checks"]["serve.logit_gap"]["value"] > res["checks"]["serve.logit_gap"]["limit"]


def test_command_refuses_without_a_chip(root, tmp_path):
    cmd = [sys.executable, str(root / "bench" / "run.py"), "--workload", "tiny-cell",
           "--seed", str(SEED), "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=root, capture_output=True, text=True,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()
    # a directory with only the benchmark's files holds no program to run
    (tmp_path / "BENCHMARK.json").write_text((root / "BENCHMARK.json").read_text())
    p = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()
    json.loads((root / "BENCHMARK.json").read_text())
