"""Plain reference of a dense decoder whose float32 training state fits no
single chip (OLMo-1B: params, gradients and AdamW moments ≈ 18.8 GB).

The equations are ``dense.py``'s, imported from it (weights, norms, rotary
embedding, matrix products, learning-rate schedule, serving gaps); only the
order of the work differs:

* the loss gradient of a batch is the sum over its rows of each row's
  gradient (the mean over ``B × S`` tokens, exact up to summation order), so
  one row's activations are live at a time;
* the layers run as a scan, each rematerialised in the backward pass
  (``jax.checkpoint``), so the pass compiles once a layer;
* params, AdamW moments and gradients are placed over the configuration's
  chips (``train.mesh``), each leaf split along its largest dimension the
  chip count divides (never the stacked layer axis); computation follows
  the placement.

It imports nothing of the system under test.  With ``"fp8"`` the operands
of each product are rounded to float8 per tensor as in ``dense.py``, the
activations' scales taken per row.
"""
from __future__ import annotations

import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _load_dense():
    path = Path(__file__).with_name("dense.py")
    spec = importlib.util.spec_from_file_location("bench_reference_dense_eqs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dense = _load_dense()
Matmul, dims = dense.Matmul, dense.dims
init_params, serve_gaps = dense.init_params, dense.serve_gaps


# ---------------------------------------------------------------------------
# Placement over the chips
# ---------------------------------------------------------------------------


def _chips(c: dict) -> int:
    m = c["train"]["mesh"]
    return m["data"] * m["model"]


def shardings(c: dict, tree) -> dict:
    """Per leaf: split along its largest dimension that the chip count
    divides, the stacked layer axis excepted; replicated if none does."""
    n = _chips(c)
    mesh = Mesh(np.array(jax.devices()[:n]), ("chips",))

    def one(path, x):
        first = 1 if path and getattr(path[0], "key", None) == "layers" else 0
        axes = [i for i in range(first, x.ndim) if x.shape[i] % n == 0]
        if not axes:
            return NamedSharding(mesh, P())
        best = max(axes, key=lambda i: x.shape[i])
        return NamedSharding(mesh, P(*[("chips" if i == best else None)
                                       for i in range(x.ndim)]))

    return jax.tree_util.tree_map_with_path(one, tree)


# ---------------------------------------------------------------------------
# Forward pass: dense.hidden's layers as a scan
# ---------------------------------------------------------------------------


def _layer(c: dict, mm, x, lp, pos):
    """One layer of ``dense.hidden``."""
    n = dims(c)
    H, kvH, hd = n["H"], n["kvH"], n["hd"]
    causal = pos[None, :] <= pos[:, None]
    a = lp["attn"]
    h = dense._norm(c, x, lp.get("ln1"))
    q = mm("bsd,dhk->bshk", h, a["wq"])
    k = mm("bsd,dhk->bshk", h, a["wk"])
    v = mm("bsd,dhk->bshk", h, a["wv"])
    if "q_norm" in a:
        q = dense._norm(c, q, a["q_norm"])
        k = dense._norm(c, k, a["k_norm"])
    q, k = dense._rope(q, pos, c["rope_theta"]), dense._rope(k, pos, c["rope_theta"])
    k = jnp.repeat(k, H // kvH, axis=2)
    v = jnp.repeat(v, H // kvH, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) * hd**-0.5
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = mm("bhqk,bkhd->bqhd", probs, v)
    x = x + mm("bshk,hkd->bsd", o, a["wo"])
    h = dense._norm(c, x, lp.get("ln2"))
    f = lp["ffn"]
    g = jax.nn.silu(mm("bsd,df->bsf", h, f["wg"])) * mm("bsd,df->bsf", h, f["wu"])
    return x + mm("bsf,fd->bsd", g, f["wd"])


def _hidden(c: dict, p: dict, tokens, mm):
    """``dense.hidden`` with its loop over layers as a scan, each layer
    recomputed in the backward pass."""
    pos = jnp.arange(tokens.shape[1])
    x = p["embed"][tokens].astype(jnp.float32)
    layer = jax.checkpoint(lambda x, lp: (_layer(c, mm, x, lp, pos), None))
    x, _ = jax.lax.scan(layer, x, p["layers"])
    return dense._norm(c, x, p.get("final_norm"))


# this module's copy of dense.py runs its logits, loss and serving gaps on
# the scanned pass: the same equations, compiled once a layer instead of
# once for every layer
dense.hidden = _hidden


def _adamw(t: dict, step, p, mu, nu, g):
    """Clip and AdamW, as ``dense._train_step`` does after its gradient."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, t["grad_clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, eps, wd = t["beta1"], t["beta2"], t["eps"], t["weight_decay"]
    n = step + 1
    c1, c2 = 1.0 - b1**n, 1.0 - b2**n
    lr = dense.lr_at(t, step)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    p = jax.tree.map(
        lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * w), p, mu, nu)
    return p, mu, nu, gnorm, dense.leaf_norms(g)


def train_readings(c: dict, p0: dict, batches: list, mode: str) -> dict:
    """``dense.train_readings``, computed a row at a time over the chips:
    the loss and the global gradient norm before clipping of each step, the
    norm of every leaf of the first clipped gradient, and the norm of every
    leaf of the parameters' change over all the steps."""
    sh = shardings(c, p0)
    one = NamedSharding(sh["embed"].mesh, P())
    B, S = batches[0]["inputs"].shape
    mm = Matmul(mode)
    # one row's share of the batch's mean loss, and its gradient
    row_grad = jax.jit(jax.value_and_grad(lambda q, x, y: dense.loss(c, q, x, y, mm) / B),
                       out_shardings=(one, sh))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), out_shardings=sh,
                  donate_argnums=(0,))
    adamw = jax.jit(functools.partial(_adamw, c["train"]), out_shardings=(sh, sh, sh, one, one),
                    donate_argnums=(1, 2, 3, 4))
    start = jax.device_put(p0, sh)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t), out_shardings=sh)
    p, mu, nu = jax.jit(lambda t: jax.tree.map(jnp.copy, t), out_shardings=sh)(start), \
        zeros(start), zeros(start)
    losses, gnorms, first = [], [], None
    for i, b in enumerate(batches):
        value, g = 0.0, None
        for r in range(B):
            v, gr = row_grad(p, jax.device_put(np.asarray(b["inputs"])[r:r + 1], one),
                             jax.device_put(np.asarray(b["labels"])[r:r + 1], one))
            value += float(v)
            g = gr if g is None else add(g, gr)
        p, mu, nu, gnorm, gl = adamw(jnp.float32(i), p, mu, nu, g)
        losses.append(value)
        gnorms.append(float(gnorm))
        if first is None:
            first = [float(x) for x in gl]
    change = [float(x) for x in jax.jit(
        lambda a, b: dense.leaf_norms(jax.tree.map(jnp.subtract, a, b)))(p, start)]
    stats = [d.memory_stats() or {} for d in jax.devices()[:_chips(c)]]
    print(f"reference ({mode}): device memory in use / peak, GiB: "
          + ", ".join(f"{s.get('bytes_in_use', 0) / 2**30:.2f} / "
                      f"{s.get('peak_bytes_in_use', 0) / 2**30:.2f}" for s in stats),
          file=sys.stderr, flush=True)
    return {"loss": losses, "gnorm": gnorms, "grad_leaf": first, "change_leaf": change}
