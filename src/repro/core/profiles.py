"""Training-iteration and inference profiles: the bubble timelines
``SpecInFRuntime`` fills and the timeline simulator replays.

Two sources:
  * measured  -- ``dp_profile`` / ``mp_profile`` / ``pp_profile`` from a
    step's compute and communication seconds (the benchmark sizes its
    ``dp_profile`` from a step measured on the chip);
  * analytic  -- 6ND-style napkin math over a HardwareSpec
    (``analytic_iteration``, ``analytic_inference_profile``).

A profile is the per-iteration segment structure one accelerator observes:
alternating (compute | bubble) spans.  Parallel modes shape it differently
(paper §2.1): DP exposes one gradient-sync tail bubble; MP/TP exposes
many short per-layer collective bubbles; PP exposes per-microbatch gaps
plus warmup/drain bubbles.
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig
from repro.core.collocation import InstanceProfile, TrainingProfile
from repro.core.hardware import HardwareSpec

Segment = tuple[str, float]  # ("compute" | "bubble", seconds)


@dataclasses.dataclass(frozen=True)
class IterationProfile:
    """One training iteration's segment timeline on a single accelerator."""

    name: str
    segments: tuple[Segment, ...]
    mode: str  # "dp" | "mp" | "pp"

    @property
    def iteration_s(self) -> float:
        return sum(d for _, d in self.segments)

    @property
    def compute_s(self) -> float:
        return sum(d for k, d in self.segments if k == "compute")

    @property
    def bubble_s(self) -> float:
        return sum(d for k, d in self.segments if k == "bubble")

    @property
    def bubble_fraction(self) -> float:
        return self.bubble_s / max(self.iteration_s, 1e-12)

    @property
    def max_bubble_s(self) -> float:
        return max((d for k, d in self.segments if k == "bubble"), default=0.0)

    def as_training_profile(self, peak_memory_bytes: int) -> TrainingProfile:
        return TrainingProfile(
            name=self.name,
            peak_memory_bytes=peak_memory_bytes,
            iteration_time_s=self.iteration_s,
            max_bubble_s=self.max_bubble_s,
            bubble_fraction=self.bubble_fraction,
        )


# ---------------------------------------------------------------------------
# Segment-structure constructors
# ---------------------------------------------------------------------------


def dp_profile(
    name: str,
    compute_s: float,
    comm_s: float,
    overlap: float = 0.3,
    num_buckets: int = 2,
):
    """DP (DDP-style): backward interleaves per-bucket gradient all-reduces,
    so the exposed communication appears as a few mid-backward stalls plus a
    larger tail (last bucket + optimizer sync) — this is the multi-gap
    utilization trace of the paper's Fig. 1a."""
    exposed = comm_s * (1.0 - overlap)
    fwd = compute_s * 0.33
    bwd = compute_s * 0.67
    tail = exposed * 0.42
    fwd_gap = exposed * 0.04  # host-sync / input-pipeline hiccups in forward
    per_bucket_b = (exposed - tail - 2 * fwd_gap) / num_buckets
    per_bucket_c = bwd / num_buckets
    segs = [
        ("compute", fwd * 0.4),
        ("bubble", fwd_gap),
        ("compute", fwd * 0.6),
        ("bubble", fwd_gap),
    ]
    for _ in range(num_buckets):
        segs.append(("compute", per_bucket_c))
        segs.append(("bubble", per_bucket_b))
    segs.append(("bubble", tail))
    return IterationProfile(name, tuple(segs), "dp")


def mp_profile(name: str, compute_s: float, comm_s: float, num_layers: int):
    """MP/TP: per-layer compute followed by a short activation collective.
    2 collectives per layer fwd + 2 bwd (Megatron pairing)."""
    n = max(num_layers, 1)
    c, b = compute_s / n, comm_s / n
    segs = tuple(
        seg for _ in range(n) for seg in (("compute", c), ("bubble", b))
    )
    return IterationProfile(name, segs, "mp")


def pp_profile(
    name: str, compute_s: float, comm_s: float, num_microbatches: int = 12,
):
    """PP: warmup/drain bubbles at iteration boundaries (~35% of exposed
    idle) plus per-microbatch send gaps.  Dividing the mini-batch into
    microbatches shortens each gap to the edge of monitor detectability —
    the paper's stated reason SpecInF's PP gains are marginal (§5.2)."""
    m = max(num_microbatches, 1)
    warm = comm_s * 0.35
    per_mb_c = compute_s / m
    per_mb_b = comm_s * 0.65 / m
    segs = [("bubble", warm * 0.5)]
    for _ in range(m):
        segs.append(("compute", per_mb_c))
        segs.append(("bubble", per_mb_b))
    segs.append(("bubble", warm * 0.5))
    return IterationProfile(name, tuple(segs), "pp")


# ---------------------------------------------------------------------------
# Analytic estimation from model configs (paper-fidelity benches)
# ---------------------------------------------------------------------------


def train_flops(cfg: ModelConfig, tokens: int) -> float:
    """6 * N_active * D."""
    return 6.0 * cfg.active_param_count() * tokens


def analytic_iteration(
    cfg: ModelConfig,
    *,
    seq_len: int,
    per_device_batch: int,
    num_devices: int,
    mode: str,
    hw: HardwareSpec,
    overlap: float = 0.3,
    target_bubble_fraction: float | None = None,
) -> IterationProfile:
    """``target_bubble_fraction``: calibrate exposed communication to a
    *measured* idle fraction (the paper's Fig. 1 traces: ~0.30 for DP, ~0.35
    for MP, ~0.15 for PP) instead of the idealized link-peak estimate —
    production all-reduces at DDP message sizes never reach link peak."""
    tokens = per_device_batch * seq_len
    compute_s = train_flops(cfg, tokens) / (hw.peak_flops * hw.mfu_assumption)
    p_bytes = cfg.param_count() * 2  # bf16 grads on the wire
    if target_bubble_fraction is not None:
        f = target_bubble_fraction
        exposed = compute_s * f / (1.0 - f)
        if mode == "dp":
            return dp_profile(cfg.name, compute_s, exposed, overlap=0.0)
        if mode == "mp":
            return mp_profile(cfg.name, compute_s, exposed, cfg.num_layers)
        if mode == "pp":
            return pp_profile(cfg.name, compute_s, exposed)
        raise ValueError(mode)
    if mode == "dp":
        # ring all-reduce: 2 * size * (n-1)/n per device
        comm_s = 2 * p_bytes * (num_devices - 1) / num_devices / hw.link_bandwidth
        return dp_profile(cfg.name, compute_s, comm_s, overlap)
    if mode == "mp":
        # Megatron TP: 4 all-reduces of [B, S, d] activations per layer
        act = per_device_batch * seq_len * cfg.d_model * 2
        per_ar = 2 * act * (num_devices - 1) / num_devices / hw.link_bandwidth
        comm_s = 4 * per_ar * cfg.num_layers
        return mp_profile(cfg.name, compute_s, comm_s, cfg.num_layers)
    if mode == "pp":
        act = per_device_batch * seq_len * cfg.d_model * 2
        comm_s = 2 * act / hw.link_bandwidth  # boundary sends fwd+bwd
        return pp_profile(cfg.name, compute_s, comm_s)
    raise ValueError(mode)


def analytic_inference_profile(
    cfg: ModelConfig,
    *,
    batch: int,
    seq_or_context: int,
    hw: HardwareSpec,
    kind: str = "decode",
    online: bool = False,
    name: str | None = None,
) -> InstanceProfile:
    """Memory + latency footprint of one inference microstep.

    decode: one token for ``batch`` slots against a ``seq_or_context`` cache —
    memory-bandwidth-bound (reads all active params + cache).
    batch_infer: one full forward at ``seq_or_context`` length (offline
    classification-style microstep; compute-bound).
    """
    p_bytes = cfg.active_param_count() * 2
    if kind == "decode":
        hd = cfg.resolved_head_dim
        cache_bytes = (
            cfg.num_layers * 2 * cfg.num_kv_heads * hd * seq_or_context * batch * 2
            if cfg.num_kv_heads
            else cfg.num_layers * cfg.d_inner * cfg.ssm_state * batch * 4
        )
        latency = (p_bytes + cache_bytes) / hw.hbm_bandwidth
        mem = p_bytes + cache_bytes
    else:
        tokens = batch * seq_or_context
        flops = 2.0 * cfg.active_param_count() * tokens
        latency = flops / (hw.peak_flops * hw.mfu_assumption)
        mem = p_bytes + tokens * cfg.d_model * 8  # activations
    return InstanceProfile(
        name=name or f"{cfg.name}-{kind}",
        peak_memory_bytes=int(mem),
        min_exec_time_s=float(latency),
        online=online,
    )
