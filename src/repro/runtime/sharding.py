"""Partition-spec rules: DP / TP / EP / SP / FSDP over a device mesh.

Axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod.  Batch rides ``(pod, data)``; weights ride ``model``:

  * TP (Megatron pairing): attention heads + FFN hidden on ``model`` —
    column-parallel in (wq/wk/wv, wg/wu), row-parallel out (wo, wd), so each
    block costs one fwd all-reduce + one bwd all-reduce.
  * EP: MoE expert dim on ``model``; router replicated.
  * Vocab: embedding + LM head sharded on ``model`` (the loss's logsumexp
    reduces over the sharded vocab with one small psum).
  * SP (decode): when the KV-head count does not divide ``model``, the KV
    cache shards its *sequence* dim on ``model`` instead — GSPMD then lowers
    decode softmax to flash-decode semantics (local partial stats + tiny
    psum) rather than gathering the cache.
  * FSDP: parameters/moments additionally shard a large *free* dim over
    ``data`` (ZeRO-3 via GSPMD; all-gather per scan step, reduce-scatter in
    backward).  Enabled for training and for serve-weights that exceed a
    per-chip budget.

**Divisibility rule** (jit argument shardings must divide exactly): a dim is
sharded only when ``dim % axis_size == 0``; otherwise the dim stays
replicated and (for big tensors) FSDP covers memory.  Consequences recorded
in DESIGN.md: qwen2-7b (28H/kv4) and deepseek-coder-33b (56H/kv8) run
attention replicated over ``model`` in the baseline — the §Perf hillclimb
adds physical head padding to recover TP there.

Specs are assigned by parameter-path pattern over the real pytree, so new
weights fail loudly (no silent replication of a TB-scale tensor): any leaf
with >= 2 dims must match a rule.
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig

Tree = Any

FSDP_MIN_ELEMENTS = 1 << 20  # don't bother FSDP-sharding tiny leaves


def dp_axes(mesh, layout: str = "tp") -> tuple:
    """Axes carrying the batch.  ``dp256`` folds the model axis into data
    parallelism (pure DP + ZeRO-3) — the §Perf layout for small archs where
    TP's activation collectives dwarf their compute."""
    if layout == "dp256":
        return tuple(
            a for a in mesh.axis_names if a in ("pod", "data", "model")
        )
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


class ShardingPlan:
    """Divisibility-resolved axis choices for one (cfg, mesh, layout)."""

    def __init__(self, cfg: ModelConfig, mesh, layout: str = "tp"):
        self.cfg = cfg
        self.layout = layout
        self.model = axis_size(mesh, "model") if layout == "tp" else 1
        self.data = axis_size(mesh, "data")
        m = self.model
        h_phys = cfg.num_heads_physical
        self.heads_shardable = m > 1 and h_phys > 0 and h_phys % m == 0
        self.kv_shardable = m > 1 and cfg.num_kv_heads > 0 and cfg.num_kv_heads % m == 0
        self.ff_shardable = m > 1 and cfg.d_ff > 0 and cfg.d_ff % m == 0
        self.vocab_shardable = m > 1 and cfg.vocab_size % m == 0
        self.di_shardable = (
            m > 1 and cfg.d_inner % m == 0 if cfg.ssm_state else False
        )
        self.experts_shardable = (
            m > 1 and cfg.num_experts > 0 and cfg.num_experts % m == 0
        )

    def h(self):  # attention q/o head axis
        return "model" if self.heads_shardable else None

    def kv(self):  # attention k/v head axis
        return "model" if self.kv_shardable else None

    def ff(self):
        return "model" if self.ff_shardable else None

    def vocab(self):
        return "model" if self.vocab_shardable else None

    def di(self):
        return "model" if self.di_shardable else None

    def e(self):
        return "model" if self.experts_shardable else None


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------


def _param_rule(path: str, ndim: int, cfg: ModelConfig, plan: ShardingPlan) -> P:
    """Spec for one parameter leaf.  Leading stacked-layer dims (scan) are
    unsharded; the rule applies to the trailing weight dims."""
    stack = 0
    if path.startswith("layers/"):
        stack = 2 if cfg.family == "hybrid" else 1
    lead = (None,) * stack
    trailing = ndim - stack

    def spec(*tail):
        assert len(tail) == trailing, (path, ndim, tail)
        return P(*lead, *tail)

    if re.search(r"(^|/)embed$", path):
        return P(plan.vocab(), None)
    if re.search(r"(^|/)lm_head$", path):
        return P(None, plan.vocab())
    if re.search(r"final_norm$", path):
        return P(None)
    # --- attention ---
    if re.search(r"attn/wq$", path):
        return spec(None, plan.h(), None)  # [d, H, hd]
    if re.search(r"attn/w[kv]$", path):
        return spec(None, plan.kv(), None)  # [d, kvH, hd]
    if re.search(r"attn/wo$", path):
        return spec(plan.h(), None, None)  # [H, hd, d]
    if re.search(r"attn/bq$", path):
        return spec(plan.h(), None)
    if re.search(r"attn/b[kv]$", path):
        return spec(plan.kv(), None)
    if re.search(r"attn/(q|k)_norm$", path):
        return spec(None)
    # --- dense MLP ---
    if re.search(r"ffn/w[gu]$", path) and cfg.family != "moe":
        return spec(None, plan.ff())
    if re.search(r"ffn/wd$", path) and cfg.family != "moe":
        return spec(plan.ff(), None)
    # --- MoE (expert parallel) ---
    if re.search(r"ffn/router$", path):
        return spec(None, None)
    if re.search(r"ffn/w[gud]$", path):
        return spec(plan.e(), None, None)  # [E, d, f] / [E, f, d]
    # --- norms ---
    if re.search(r"ln\d?$", path) or re.search(r"/ln$", path):
        return spec(None)
    # --- mamba1 ---
    if re.search(r"mixer/in_proj$", path):
        return spec(None, plan.di())
    if re.search(r"mixer/(conv_w|conv_x_w|conv_bc_w)$", path):
        return spec(None, plan.di()) if "bc" not in path else spec(None, None)
    if re.search(r"mixer/(conv_b|conv_x_b)$", path):
        return spec(plan.di())
    if re.search(r"mixer/conv_bc_b$", path):
        return spec(None)
    if re.search(r"mixer/x_proj$", path):
        return spec(plan.di(), None)
    if re.search(r"mixer/dt_proj$", path):
        return spec(None, plan.di())
    if re.search(r"mixer/dt_bias$", path):
        return spec(plan.di()) if cfg.ssm_version == 1 else spec(None)
    if re.search(r"mixer/A_log$", path):
        return spec(plan.di(), None) if cfg.ssm_version == 1 else spec(None)
    if re.search(r"mixer/D$", path):
        return spec(plan.di()) if cfg.ssm_version == 1 else spec(None)
    if re.search(r"mixer/out_proj$", path):
        return spec(plan.di(), None)
    # --- mamba2 ---
    if re.search(r"mixer/in_proj_zx$", path):
        return spec(None, plan.di())
    if re.search(r"mixer/in_proj_bcdt$", path):
        return spec(None, None)
    if re.search(r"mixer/gate_norm$", path):
        return spec(plan.di())
    raise ValueError(f"no sharding rule for parameter {path!r} (ndim={ndim})")


def _add_fsdp(
    spec: P, shape: tuple, stack: int, data_size: int,
    axes: tuple = ("data",), axis_sizes: dict | None = None,
) -> P:
    """Shard the largest still-free trailing dim over the fsdp ``axes``
    (ZeRO-3).  With ``axes=("data", "model")`` (dp256 layout) it tries the
    joint product first, then each axis separately on distinct dims."""
    if data_size <= 1:
        return spec
    n_el = 1
    for d in shape:
        n_el *= d
    if n_el < FSDP_MIN_ELEMENTS:
        return spec
    sizes = axis_sizes or {"data": data_size}
    parts = list(spec) + [None] * (len(shape) - len(spec))

    def place(ax_group) -> bool:
        size = 1
        for a in ax_group:
            size *= sizes.get(a, 1)
        best, best_dim = -1, -1
        for i in range(stack, len(shape)):
            if parts[i] is None and shape[i] % size == 0 and shape[i] > best:
                best, best_dim = shape[i], i
        if best_dim >= 0:
            parts[best_dim] = ax_group if len(ax_group) > 1 else ax_group[0]
            return True
        return False

    if len(axes) > 1 and place(tuple(axes)):
        return P(*parts)
    for a in axes:
        place((a,))
    if any(p is not None for p in parts[stack:]) or spec != P(*parts):
        return P(*parts)
    return spec


def param_specs(
    cfg: ModelConfig, params_shape: Tree, *, mesh, fsdp: bool = False,
    layout: str = "tp",
) -> Tree:
    """PartitionSpec tree mirroring ``params_shape`` (shapes or arrays)."""
    plan = ShardingPlan(cfg, mesh, layout)
    data_size = axis_size(mesh, "data")
    fsdp_axes = ("data", "model") if layout == "dp256" else ("data",)
    axis_sizes = {a: axis_size(mesh, a) for a in ("data", "model")}

    def assign(path, leaf):
        p = _path_str(path)
        spec = _param_rule(p, len(leaf.shape), cfg, plan)
        if fsdp:
            stack = 0
            if p.startswith("layers/"):
                stack = 2 if cfg.family == "hybrid" else 1
            spec = _add_fsdp(
                spec, leaf.shape, stack, data_size,
                axes=fsdp_axes, axis_sizes=axis_sizes,
            )
        return spec

    return jax.tree_util.tree_map_with_path(assign, params_shape)


def opt_state_specs(
    cfg: ModelConfig, params_shape: Tree, zero1: bool, mesh, *,
    fsdp: bool = False, layout: str = "tp",
) -> Tree:
    """AdamW moment specs.  With ``zero1`` the moments additionally shard
    over ``data`` on the first dim that divides evenly (ZeRO-1: sharded
    optimizer update, GSPMD all-gathers the fresh params)."""
    base = param_specs(cfg, params_shape, mesh=mesh, fsdp=fsdp, layout=layout)
    if not zero1:
        mom = base
    else:
        data_size = axis_size(mesh, "data")

        def add_data(path, leaf, spec):
            parts = list(spec)
            parts += [None] * (len(leaf.shape) - len(parts))
            used = set()
            for pt in parts:
                if pt is not None:
                    used |= set(pt if isinstance(pt, tuple) else (pt,))
            if "data" in used:  # fsdp already covers it
                return spec
            for i, (dim, ax) in enumerate(zip(leaf.shape, parts)):
                if ax is None and dim % data_size == 0 and dim >= data_size:
                    parts[i] = "data"
                    return P(*parts)
            return spec

        mom = jax.tree_util.tree_map_with_path(add_data, params_shape, base)
    return {"mu": mom, "nu": mom, "step": P()}


def state_specs(
    cfg: ModelConfig, state_shape: Tree, *, zero1: bool, mesh, fsdp: bool = False
) -> Tree:
    return {
        "params": param_specs(cfg, state_shape["params"], mesh=mesh, fsdp=fsdp),
        "opt": opt_state_specs(
            cfg, state_shape["params"], zero1, mesh, fsdp=fsdp
        ),
    }


# ---------------------------------------------------------------------------
# Data / cache specs
# ---------------------------------------------------------------------------


def batch_specs(
    cfg: ModelConfig, shape: Optional[ShapeConfig], mesh, layout: str = "tp"
) -> Tree:
    dp = dp_axes(mesh, layout)
    spec = {"labels": P(dp, None)}
    if cfg.embed_inputs:
        spec["inputs"] = P(dp, None, None)
    else:
        spec["inputs"] = P(dp, None)
    return spec


def cache_specs(cfg: ModelConfig, cache_shape: Tree, shape: ShapeConfig, mesh) -> Tree:
    """Decode-cache specs.

    Batch rides (pod, data) when it covers the axis; otherwise (long-context
    batch=1) the sequence dim rides it.  KV heads ride ``model`` when they
    divide it; otherwise the cache *sequence* dim rides ``model`` instead
    (flash-decode sequence parallelism)."""
    plan = ShardingPlan(cfg, mesh)
    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_shardable = (
        shape.global_batch % dp_size == 0 and shape.global_batch >= dp_size
    )
    b_ax = dp if batch_shardable else None
    # attention cache: prefer kv-head sharding on model; else seq on model;
    # when batch can't cover (pod, data), seq takes dp instead.
    if plan.kv_shardable:
        kvh_ax, s_model = "model", None
    else:
        kvh_ax, s_model = None, "model"
    s_ax: Any = s_model
    if not batch_shardable:
        s_ax = (dp + (s_model,)) if s_model else dp
        if isinstance(s_ax, tuple) and len(s_ax) == 1:
            s_ax = s_ax[0]

    def assign(path, leaf):
        p = _path_str(path)
        nd = len(leaf.shape)
        if p == "index":
            return P() if nd == 0 else P(b_ax)
        # attention kv caches: [L, B, S, kvH, hd] (or [C, B, S, kvH, hd] hybrid)
        if re.search(r"(^|/)(k|v|shared_k|shared_v)$", p):
            return P(None, b_ax, s_ax, kvh_ax, None)
        # mamba states (leading stack dims: 1 for ssm, 2 for hybrid)
        stack = 2 if cfg.family == "hybrid" else 1
        lead = (None,) * stack
        if p.endswith("conv") or p.endswith("conv_x"):
            return P(*lead, b_ax, None, plan.di())
        if p.endswith("conv_bc"):
            return P(*lead, b_ax, None, None)
        if p.endswith("/h") or p == "h":
            if cfg.family == "hybrid":  # [C, k, B, nh, hp, ds]
                return P(*lead, b_ax, plan.di(), None, None)
            return P(*lead, b_ax, plan.di(), None)  # [L, B, di, ds]
        raise ValueError(f"no cache sharding rule for {p!r}")

    return {
        "index": P(),
        "layers": jax.tree_util.tree_map_with_path(
            lambda pth, l: assign(pth, l), cache_shape["layers"]
        ),
    }


def logits_spec(cfg: ModelConfig, mesh) -> P:
    plan = ShardingPlan(cfg, mesh)
    return P(dp_axes(mesh), None, plan.vocab())


def activation_specs(
    cfg: ModelConfig, mesh, *, batch_sharded: bool = True, layout: str = "tp"
) -> dict:
    """Kind -> PartitionSpec table for ``models.act_sharding.shard``.

    Kinds (leading ``b`` = batch, ``t`` = seq/time position):
      btd   [B, S, d_model]      residual stream
      bthd  [B, S, H, hd]        q / attention out, heads on model
      btkv  [B, S, kvH, hd]      k / v
      btf   [B, S, d_ff]         MLP hidden
      btv   [B, S, vocab]        logits
      bti   [B, S, d_inner]      mamba inner stream
      becd  [B, E, C, d]         MoE expert buffer (experts on model)
      bv    [B, vocab]           decode logits
    """
    plan = ShardingPlan(cfg, mesh, layout)
    b = dp_axes(mesh, layout) if batch_sharded else None
    return {
        "btd": P(b, None, None),
        "bthd": P(b, None, plan.h(), None),
        "btkv": P(b, None, plan.kv(), None),
        "btf": P(b, None, plan.ff()),
        "btv": P(b, None, plan.vocab()),
        "bti": P(b, None, plan.di()),
        "bi": P(b, plan.di()),
        "ecd": P(plan.e(), None, None),  # inside vmap over batch groups
        "bv": P(b, plan.vocab()),
        # flash-attention scan carries ([B, H, S, hd] / [B, H, S])
        "bhtd": P(b, plan.h(), None, None),
        "bht": P(b, plan.h(), None),
    }


def named(mesh, spec_tree: Tree) -> Tree:
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )
