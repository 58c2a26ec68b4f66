"""A tiny copy of the benchmark for rehearsals on the CPU: the same harness
and files, plus a tiny configuration and traffic mix added as new files and
entries, in a scratch root."""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "source": "tiny rehearsal of qwen3-1.7b-l4", "reduced": [], "reference": "dense",
    "family": "dense", "norm": "rmsnorm", "qk_norm": True,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "rope_theta": 10000, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
    "train": {"mesh": {"data": 1, "model": 1}, "param_dtype": "float32",
              "compute_dtype": "bfloat16", "remat_policy": "full", "fsdp": False,
              "zero1": False, "seq_len": 16, "global_batch": 4, "learning_rate": 0.001,
              "warmup_steps": 1, "total_steps": 1000, "schedule": "cosine",
              "weight_decay": 0.1, "beta1": 0.9, "beta2": 0.95, "eps": 1e-08,
              "grad_clip_norm": 1.0},
    # sound tiny runs on the CPU read at most 7e-4 (train) and 0.008
    # (serve); the float8 control reads 0.0021, 0.015, 0.014 and 0.25
    "limits": {"train.loss_gap": 0.0015, "train.gnorm_gap": 0.004, "train.grad_gap": 0.004,
               "train.update_gap": 0.004, "serve.logit_gap": 0.05},
}

TINY_TRAFFIC = {
    "profile": {"kind": "dp", "compute_s": 0.05, "comm_s": 0.025, "overlap": 0.3},
    "decode_microstep_s": 0.004, "engine": {"max_slots": 4, "max_seq": 64}, "block": 8,
    "offline": {"prompt": {"dist": "loguniform", "min": 8, "max": 24},
                "output": {"dist": "uniform", "min": 4, "max": 12}, "queue_depth": 4},
    "online": {"rate_per_s": 4.0, "prompt": {"dist": "loguniform", "min": 8, "max": 24},
               "output": {"dist": "loguniform", "min": 4, "max": 12}, "drain_s": 30},
    "warmup": {"requests": 2, "prompt": 20, "output": 20, "k_buckets": [1, 2, 4, 8]},
}


def make_root(tmp: Path, online: bool = True) -> Path:
    """A checkout-like root: the repo's BENCHMARK.json with one tiny config
    and one tiny cell added, the bench files copied, the program linked."""
    root = Path(tmp)
    shutil.copytree(REPO / "bench", root / "bench", ignore=shutil.ignore_patterns("tests"))
    os.symlink(REPO / "src", root / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = dict(TINY_CONFIG, name="tiny")
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    traffic = dict(TINY_TRAFFIC)
    if not online:
        traffic.pop("online")
    (root / "bench" / "traffic" / "tiny_mix.json").write_text(json.dumps(traffic))
    spec["configs"].append({"name": "tiny", "source": "tiny", "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "CPU rehearsal"})
    spec["workloads"].append({"name": "tiny-cell", "config": "tiny", "traffic": "tiny_mix",
                              "chips": 1, "why": "CPU rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:  # every metric of either cell
        if "workloads" in m:
            m["workloads"].append("tiny-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
