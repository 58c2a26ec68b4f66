"""Finds what a cell needs by the names ``BENCHMARK.json`` gives.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, so adding one is adding files and
entries:

* a configuration: the file its ``configs`` entry names (sizes, training
  and serving settings, the limits of its correctness numbers), and its
  plain reference ``reference/<reference>.py``;
* a traffic mix: ``traffic/<traffic>.json``, read by ``generator.py``; its
  ``profile`` names a ``core.profiles.<kind>_profile`` and its parameters;
* a metric, end to end or per layer: ``metrics/<name>.py``, whose
  ``read(window)`` returns the number or None (the kernel of a roofline,
  and the function that counts its work, are named in its reader);
* a device's peaks: an entry of ``peaks.json`` keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Catalog:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = self.root / BENCH.name
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        cfg = json.loads((self.root / entry["file"]).read_text())
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json").read_text())

    def reference(self, name: str):
        return _load_module(self.bench / "reference" / f"{name}.py", f"bench_reference_{name}")

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.bench / "peaks.json").read_text())
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
        return table[device_kind]

    def end_to_end(self, workload: str) -> list:
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        return _load_module(self.bench / "metrics" / f"{metric}.py",
                            "bench_metric_" + metric.replace(".", "_").replace("-", "_"))
