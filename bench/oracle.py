"""The comparison that decides ``correct``.

Training: the losses and global gradient norms (before clipping) of the
first three steps the window's call ran, the norm of every leaf of the
first clipped gradient (AdamW's first moment after one step, over
``1 - beta1``), and the norm of every leaf of the parameters' change over
the three steps, against the float32 reference from the same weights and
rows.  A leaf gap is ``|program - reference|`` over the larger of the
reference's leaf norm and its median leaf norm; leaves whose reference
gradient is under a thousandth of the median leaf's are left out (none are
in these models, the rule guards leaves that only round-off moves).

Serving: for a sample of finished requests, the widest gap by which a
served token's float32 reference logit lies below the reference's best
logit at that position.

Each number is held to its limit in the configuration file.  With
``controls`` the same numbers are also read for the reference computed in
float8 put in the program's place (the control), which must fail them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NULL_LEAF = 1e-3


def _leaf_gap(prog: list, ref: list, keep: list) -> float:
    med = float(np.median(ref))
    return max(abs(p - r) / max(r, med) for p, r, k in zip(prog, ref, keep) if k)


def train_numbers(prog: dict, ref: dict) -> dict:
    med = float(np.median(ref["grad_leaf"]))
    keep = [g >= NULL_LEAF * med for g in ref["grad_leaf"]]
    return {
        "train.loss_gap": max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])),
        "train.gnorm_gap": max(abs(p - r) / r for p, r in zip(prog["gnorm"], ref["gnorm"])),
        "train.grad_gap": _leaf_gap(prog["grad_leaf"], ref["grad_leaf"], keep),
        "train.update_gap": _leaf_gap(prog["change_leaf"], ref["change_leaf"], keep),
    }


def check(c: dict, ref, key, prog: dict, batches: list, sample: list, pad_to: int,
          controls: bool = False):
    p0 = jax.jit(lambda k: ref.init_params(c, k))(key)
    dev = [{k: jnp.asarray(v) for k, v in b.items()} for b in batches]
    r32 = ref.train_readings(c, p0, dev, "f32")
    nums = train_numbers(prog, r32)
    gaps = [ref.serve_gaps(c, p0, pr, out, pad_to, control=controls) for pr, out in sample]
    nums["serve.logit_gap"] = max(g for g, _ in gaps) if gaps else float("inf")
    control = None
    if controls:
        r8 = ref.train_readings(c, p0, dev, "fp8")
        control = train_numbers(r8, r32)
        control["serve.logit_gap"] = max(x for _, x in gaps) if gaps else float("nan")
    limits = c["limits"]
    checks = {k: {"value": float(v), "limit": float(limits[k])} for k, v in nums.items()}
    return checks, control
