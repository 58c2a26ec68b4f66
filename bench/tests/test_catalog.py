"""A configuration, a traffic mix and a metric are found from new files and
an entry alone: nothing that exists is edited."""
import json
import sys
import time
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))
import devtrace  # noqa: E402
import generator  # noqa: E402
import tiny  # noqa: E402
from catalog import Catalog  # noqa: E402

#: a roofline for a kernel no existing reader knows: the reader names the
#: kernel's operations and counts its work itself
NEW_ROOFLINE = '''"""Share of its roofline of a tree-verify attention kernel."""
import work

KERNEL = "paged_tree_verify_attention"


def tree_work(c, nodes, length):
    """Each of ``nodes`` queries against ``length`` positions, in bf16."""
    hd, H, kvH = c["head_dim"], c["num_attention_heads"], c["num_key_value_heads"]
    return 4 * H * hd * nodes * length, 2 * length * kvH * hd * 2 + 2 * nodes * H * hd * 2


def read(w):
    f = b = 0
    for nodes, length in w.tree_rounds:
        tf, tb = tree_work(w.config, nodes, length)
        f, b = f + tf, b + tb
    return work.roofline(w, KERNEL, f, b)
'''


def add_new_files(root: Path) -> None:
    (root / "bench" / "metrics" / "tree_verify_attention_roofline.py").write_text(NEW_ROOFLINE)
    (root / "bench" / "metrics" / "runtime.iterations_per_s.py").write_text(
        "def read(w):\n    return w.iterations / w.window_s\n")
    mix = json.loads((root / "bench" / "traffic" / "tiny_mix.json").read_text())
    mix["profile"] = {"kind": "mp", "compute_s": 0.05, "comm_s": 0.02, "num_layers": 4}
    mix.pop("online")
    (root / "bench" / "traffic" / "tiny_mp.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-mp", "config": "tiny", "traffic": "tiny_mp",
                              "chips": 1, "why": "CPU rehearsal of tensor-parallel bubbles"})
    spec["per_layer"] += [
        {"name": "runtime.iterations_per_s", "unit": "1/s", "better": "higher",
         "source": "host_clock", "layer": "runtime", "moves": "train_tokens_per_s",
         "workloads": ["tiny-cell", "tiny-mp"]},
        {"name": "tree_verify_attention_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels", "moves": "offline_tokens_per_s",
         "workloads": ["tiny-mp"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("catalog"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    add_new_files(root)
    after = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())  # nothing existing edited
    return root


def test_new_files_and_entries_are_found(root):
    cat = Catalog(root)
    cell = cat.workload("tiny-mp")
    cfg = cat.config(cell["config"])
    assert cfg["hidden_size"] == 64 and cfg["name"] == "tiny"
    traffic = cat.traffic(cell["traffic"])
    assert traffic["profile"]["kind"] == "mp"
    reqs = generator.Traffic(traffic, cfg["vocab_size"]).offline(7)
    assert all(4 <= len(next(reqs).prompt) <= 24 for _ in range(16))
    names = [m["name"] for m in cat.per_layer("tiny-mp")]
    assert {"runtime.iterations_per_s", "tree_verify_attention_roofline"} <= set(names)
    assert "tree_verify_attention_roofline" not in [m["name"] for m in
                                                    cat.per_layer("qwen3-colloc-offline")]
    assert cat.reader("runtime.iterations_per_s").read(
        types.SimpleNamespace(iterations=30, window_s=2.0)) == 15.0
    assert cat.reference(cfg["reference"]).init_params


def test_new_roofline_reads_its_own_kernel(root):
    """The new reader finds its kernel's operations in a trace by its own
    prefix and counts its own work; a trace without them reads nothing."""
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [(host, "python3", "bench.window", 0.0, 1e6),
              (dev, devtrace.OPS, "%paged_tree_verify_attention.2 = bf16[8] custom-call()",
               0.0, 2e5),
              (dev, devtrace.OPS, "%paged_decode_attention.3 = bf16[8] custom-call()",
               3e5, 1e5)]
    cfg = Catalog(root).config("tiny")
    w = types.SimpleNamespace(config=cfg, tree_rounds=[(8, 100), (8, 300)],
                              peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                              trace=devtrace.summarize(events))
    reader = Catalog(root).reader("tree_verify_attention_roofline")
    f = sum(reader.tree_work(cfg, n, L)[0] for n, L in w.tree_rounds)
    b = sum(reader.tree_work(cfg, n, L)[1] for n, L in w.tree_rounds)
    want = 100.0 * max(f / 1e12, b / 1e9) / 2e-4
    assert reader.read(w) == pytest.approx(want)
    w.trace = devtrace.summarize(events[:1] + events[2:])
    assert reader.read(w) is None


def test_new_cell_with_another_bubble_profile_runs(root):
    """The new traffic file's ``mp`` bubbles drive a whole tiny run on the
    CPU, with nothing in the harness told of them."""
    import harness

    res = harness.run_cell("tiny-mp", 2**31 + 5, 2.0, False, root=root,
                           t_start=time.perf_counter(), require_tpu=False)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_every_entry_of_the_benchmark_resolves():
    cat = Catalog(tiny.REPO)
    for w in cat.spec["workloads"]:
        cfg = cat.config(w["config"])
        cat.reference(cfg["reference"])
        cat.traffic(w["traffic"])
        assert set(cfg["limits"]) >= {"train.loss_gap", "serve.logit_gap"}
    for m in cat.spec["per_layer"] + cat.spec["end_to_end"]:
        assert callable(cat.reader(m["name"]).read)
    assert cat.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("name", ["offline_sharegpt", "online_azure_conv"])
def test_same_work_for_every_seed(name):
    traffic = Catalog(tiny.REPO).traffic(name)
    g = generator.Traffic(traffic, 1000)
    a, b = g.offline(1), g.offline(2**31 + 11)
    la = sorted(len(next(a).prompt) for _ in range(traffic["block"]))
    lb = sorted(len(next(b).prompt) for _ in range(traffic["block"]))
    assert la == lb
    horizon = 2.5 * traffic["block"] / traffic.get("online", {}).get("rate_per_s", 1.0)
    x, y = g.online(1, horizon), g.online(2**31 + 11, horizon)
    if "online" in traffic:
        b = traffic["block"]
        ends = [(x[i].due_s, y[i].due_s) for i in range(b - 1, min(len(x), len(y)), b)]
        assert len(ends) >= 2 and all(abs(p - q) < 1e-9 for p, q in ends)
    else:
        assert x == y == []


def test_lognormal_lengths_hold_the_median_and_the_clip():
    q = generator.quantiles({"dist": "lognormal", "median": 100, "sigma": 1.0,
                             "min": 4, "max": 400}, 33)
    assert q[16] == 100 and q.min() >= 4 and q.max() == 400
    assert list(q) == sorted(q)
