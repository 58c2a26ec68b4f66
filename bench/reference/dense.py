"""Plain reference of a dense decoder (Qwen3, OLMo): weights, forward pass,
loss, gradients and AdamW, in straightforward ``jax.numpy``.

It imports nothing of the system under test.  ``init_params`` makes the
weights from the seed in the pytree layout the system takes (stacked
layers), so the benchmark can hand them to the system and make them again
here after the window.

Every matrix product goes through ``Matmul``: ``"f32"`` computes in float32
at the highest precision, ``"fp8"`` rounds both operands to float8 e4m3
with one scale per tensor first (the lower precision the control runs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _round_fp8(x):
    """x rounded to float8 e4m3 under one scale per tensor; the gradient
    passes straight through the rounding."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / F8_MAX + 1e-30)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


class Matmul:
    def __init__(self, mode: str):
        assert mode in ("f32", "fp8"), mode
        self.mode = mode

    def __call__(self, spec, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.mode == "fp8":
            a, b = _round_fp8(a), _round_fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def dims(c: dict) -> dict:
    return {
        "d": c["hidden_size"], "f": c["intermediate_size"],
        "L": c["num_hidden_layers"], "H": c["num_attention_heads"],
        "kvH": c["num_key_value_heads"], "hd": c["head_dim"],
        "V": c["vocab_size"],
    }


def init_params(c: dict, key) -> dict:
    """Random weights in float32, in the system's layout: the embedding,
    the layers stacked on a leading axis, the final norm.  Norm weights are
    ones; a non-parametric norm (OLMo) has none."""
    n = dims(c)
    d, f, L, H, kvH, hd, V = (n[k] for k in ("d", "f", "L", "H", "kvH", "hd", "V"))
    parametric = c["norm"] == "rmsnorm"
    ks = iter(jax.random.split(key, 16))
    normal = lambda shape, std: jax.random.normal(next(ks), shape, jnp.float32) * std
    attn = {
        "wq": normal((L, d, H, hd), d**-0.5),
        "wk": normal((L, d, kvH, hd), d**-0.5),
        "wv": normal((L, d, kvH, hd), d**-0.5),
        "wo": normal((L, H, hd, d), (H * hd) ** -0.5),
    }
    if c.get("qk_norm"):
        attn["q_norm"] = jnp.ones((L, hd), jnp.float32)
        attn["k_norm"] = jnp.ones((L, hd), jnp.float32)
    layers = {
        "attn": attn,
        "ffn": {
            "wg": normal((L, d, f), d**-0.5),
            "wu": normal((L, d, f), d**-0.5),
            "wd": normal((L, f, d), f**-0.5),
        },
    }
    params = {"embed": normal((V, d), d**-0.5), "layers": layers}
    if parametric:
        layers["ln1"] = jnp.ones((L, d), jnp.float32)
        layers["ln2"] = jnp.ones((L, d), jnp.float32)
        params["final_norm"] = jnp.ones((d,), jnp.float32)
    if not c.get("tie_word_embeddings"):
        raise ValueError("untied output heads are not written yet")
    return params


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def _norm(c: dict, x, w):
    if c["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c["rms_norm_eps"])
    else:  # OLMo: LayerNorm without weight or bias
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + c.get("layer_norm_eps", 1e-5))
    return y if w is None else y * w


def _rope(x, positions, theta):
    """Rotary embedding, the rotate-half convention: x [B, S, H, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(c: dict, p: dict, tokens, mm: Matmul):
    """Final-normed hidden states [B, S, d] of a causal pass over tokens."""
    n = dims(c)
    b, s = tokens.shape
    H, kvH, hd = n["H"], n["kvH"], n["hd"]
    x = p["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    for i in range(n["L"]):
        lp = jax.tree.map(lambda a: a[i], p["layers"])
        a = lp["attn"]
        h = _norm(c, x, lp.get("ln1"))
        q = mm("bsd,dhk->bshk", h, a["wq"])
        k = mm("bsd,dhk->bshk", h, a["wk"])
        v = mm("bsd,dhk->bshk", h, a["wv"])
        if "q_norm" in a:
            q = _norm(c, q, a["q_norm"])
            k = _norm(c, k, a["k_norm"])
        q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
        k = jnp.repeat(k, H // kvH, axis=2)
        v = jnp.repeat(v, H // kvH, axis=2)
        scores = mm("bqhd,bkhd->bhqk", q, k) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = mm("bhqk,bkhd->bqhd", probs, v)
        x = x + mm("bshk,hkd->bsd", o, a["wo"])
        h = _norm(c, x, lp.get("ln2"))
        f = lp["ffn"]
        g = jax.nn.silu(mm("bsd,df->bsf", h, f["wg"])) * mm("bsd,df->bsf", h, f["wu"])
        x = x + mm("bsf,fd->bsd", g, f["wd"])
    return _norm(c, x, p.get("final_norm"))


def logits(c: dict, p: dict, tokens, mm: Matmul):
    return mm("bsd,vd->bsv", hidden(c, p, tokens, mm), p["embed"])


def loss(c: dict, p: dict, inputs, labels, mm: Matmul):
    lg = logits(c, p, inputs, mm)
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


# ---------------------------------------------------------------------------
# Training: gradients, clipping, AdamW, learning-rate schedule
# ---------------------------------------------------------------------------


def lr_at(t: dict, step):
    """Linear warm-up then cosine decay, at optimizer step ``step`` (0 for
    the first update)."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(t["warmup_steps"], 1), 1.0)
    frac = jnp.clip((step - t["warmup_steps"]) / max(t["total_steps"] - t["warmup_steps"], 1),
                    0.0, 1.0)
    if t["schedule"] != "cosine":
        raise ValueError(f"unknown schedule {t['schedule']!r}")
    return t["learning_rate"] * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * frac))


def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)]


@functools.partial(jax.jit, static_argnames=("cj", "mode"), donate_argnums=(2, 3, 4))
def _train_step(cj, mode, p, mu, nu, step, inputs, labels):
    c = dict(cj)
    t = dict(c["train"])
    mm = Matmul(mode)
    value, g = jax.value_and_grad(lambda q: loss(c, q, inputs, labels, mm))(p)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, t["grad_clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, eps, wd = t["beta1"], t["beta2"], t["eps"], t["weight_decay"]
    n = step + 1
    c1, c2 = 1.0 - b1**n, 1.0 - b2**n
    lr = lr_at(t, step)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    p = jax.tree.map(
        lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * w), p, mu, nu)
    return p, mu, nu, value, gnorm, leaf_norms(g)


def freeze(c: dict):
    """A hashable copy of a config dict (static argument of a jit)."""
    if isinstance(c, dict):
        return tuple(sorted((k, freeze(v)) for k, v in c.items()))
    if isinstance(c, list):
        return tuple(freeze(v) for v in c)
    return c


def train_readings(c: dict, p0: dict, batches: list, mode: str) -> dict:
    """Run len(batches) training steps from p0.  Returns the loss and the
    global gradient norm before clipping of each step, the norm of every
    leaf of the first clipped gradient, and the norm of every leaf of the
    parameters' change over all the steps."""
    cj = freeze({k: v for k, v in c.items() if k not in ("limits", "assumed", "reduced")})
    step_fn = lambda *a: _train_step(cj, mode, *a)
    p = jax.tree.map(jnp.copy, p0)
    mu = jax.tree.map(jnp.zeros_like, p0)
    nu = jax.tree.map(jnp.zeros_like, p0)
    losses, gnorms, first = [], [], None
    for i, b in enumerate(batches):
        p, mu, nu, value, gnorm, gl = step_fn(p, mu, nu, jnp.float32(i), b["inputs"], b["labels"])
        losses.append(float(value))
        gnorms.append(float(gnorm))
        if first is None:
            first = [float(x) for x in gl]
    change = [float(x) for x in jax.jit(
        lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))(p, p0)]
    return {"loss": losses, "gnorm": gnorms, "grad_leaf": first, "change_leaf": change}


# ---------------------------------------------------------------------------
# Serving: how far below the best logit each served token lies
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cj", "mode"))
def _gaps(cj, mode, p, seq, lo, hi):
    """seq [1, S] = prompt + served tokens (zero-padded); positions
    lo..hi-1 predict seq[pos + 1].  Returns, per position, the gap between
    the best float32 logit and the logit of the served token, and the gap
    of the token that ``mode`` ranks first."""
    c = dict(cj)
    h = hidden(c, p, seq, Matmul("f32"))[0]  # [S, d]
    ref = jnp.einsum("sd,vd->sv", h, p["embed"], precision=HIGHEST)
    best = jnp.max(ref, -1)
    served = jnp.concatenate([seq[0, 1:], seq[0, :1]])
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if mode == "fp8":
        h8 = hidden(c, p, seq, Matmul("fp8"))[0]
        top = jnp.argmax(Matmul("fp8")("sd,vd->sv", h8, p["embed"]), -1)
        ctl = best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
    else:
        ctl = jnp.zeros_like(gap)
    pos = jnp.arange(seq.shape[1])
    inside = (pos >= lo) & (pos < hi)
    return jnp.max(jnp.where(inside, gap, -jnp.inf)), jnp.max(jnp.where(inside, ctl, -jnp.inf))


def serve_gaps(c: dict, p: dict, prompt, served, pad_to: int, control: bool = False):
    """Widest gap over a request's served tokens (and the control's widest
    gap at the same positions when ``control``)."""
    import numpy as np

    n = len(prompt)
    seq = np.zeros((1, pad_to), np.int32)
    full = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served, np.int32)])
    seq[0, : len(full)] = full
    cj = freeze({k: v for k, v in c.items() if k not in ("limits", "assumed", "reduced", "train")})
    gap, ctl = _gaps(cj, "fp8" if control else "f32", p, jnp.asarray(seq),
                     jnp.int32(n - 1), jnp.int32(len(full) - 1))
    return float(gap), float(ctl)
