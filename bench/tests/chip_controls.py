"""Chip readings that set the limits of the correctness check: for each
seed, one short run of a cell that reads the program's numbers and the
control's (the float8 reference in the program's place); then, with
``--fault``, runs with the timed path broken underneath.

  python3 bench/tests/chip_controls.py <workload> <seconds> <seed>... [--fault half_batch|token]

One process holds the chip for every run; each run prints one JSON line.
The benchmark's own runs never run this.
"""
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import harness  # noqa: E402


def half_batch():
    from repro.models import transformer as T

    lm_loss = T.lm_loss

    def half(cfg, params, inputs, labels, **kw):
        n = inputs.shape[0] // 2
        return lm_loss(cfg, params, inputs[:n], labels[:n], **kw)

    T.lm_loss = half


def altered_token():
    from repro.serving.engine import InferenceEngine

    drive = InferenceEngine._drive_decode_loop

    def altered(self, k):
        out = drive(self, k)
        for r in self.slots:
            if r is not None and r.generated:
                r.generated[-1] = (r.generated[-1] + 1) % self.cfg.vocab_size
                break
        return out

    InferenceEngine._drive_decode_loop = altered


def main():
    args = sys.argv[1:]
    fault = None
    if "--fault" in args:
        i = args.index("--fault")
        fault = args[i + 1]
        del args[i:i + 2]
    workload, seconds, seeds = args[0], float(args[1]), [int(s) for s in args[2:]]
    sys.path.insert(0, str(Path.cwd() / "src"))
    if fault == "half_batch":
        half_batch()
    elif fault == "token":
        altered_token()
    for seed in seeds:
        t0 = time.perf_counter()
        res = harness.run_cell(workload, seed, seconds, False, root=Path.cwd(), t_start=t0,
                               controls=fault is None)
        print(json.dumps({"workload": workload, "seed": seed, "fault": fault,
                          "correct": res["correct"], "metrics": res["metrics"],
                          "checks": res["checks"],
                          "control": res.get("readings", {}).get("control")}), flush=True)


if __name__ == "__main__":
    main()
