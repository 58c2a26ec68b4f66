"""Ahead-of-time compiles for a described TPU v5e, at real widths.

The Pallas interpreter that the other kernel tests use applies none of the
TPU lowering rules (block-shape tiling, Mosaic's supported ops, VMEM
limits).  These tests hand the kernels and one training step to the TPU
compiler for a ``v5e:2x2`` topology that is described, not attached: what
the compiler refuses here, the chip would refuse too.  Nothing runs, so
they say nothing about results or speed.

Widths are the serving path's own: qwen3-1.7b attention (16 query heads, 8
kv heads, head_dim 128) in bf16, the engine's page size and prefill chunk,
the dense kernels' default ``block_k``; falcon-mamba-7b's selective scan.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro import configs
from repro.configs.base import ShapeConfig, TrainConfig
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.paged_prefill_attention import paged_prefill_attention
from repro.kernels.paged_tree_verify_attention import (
    paged_tree_verify_attention,
)
from repro.kernels.paged_verify_attention import paged_verify_attention
from repro.kernels.prefill_attention import prefill_attention
from repro.kernels.ssm_scan import ssm_scan_chunk
from repro.kernels.tree_verify_attention import tree_verify_attention
from repro.kernels.verify_attention import verify_attention
from repro.models.ssm import DEFAULT_CHUNK as SSM_CHUNK
from repro.runtime.step import make_train_step
from repro.serving.engine import DEFAULT_KV_PAGE_SIZE, DEFAULT_PREFILL_CHUNK

QWEN3 = configs.get_config("qwen3-1.7b")
H, KVH, HD = QWEN3.num_heads, QWEN3.num_kv_heads, QWEN3.resolved_head_dim
B = 8  # serving slots
S = 512  # dense cache length (two 256-token KV tiles)
PAGE = DEFAULT_KV_PAGE_SIZE
W = S // PAGE + 1  # block-table columns, the last one the overflow sentinel
POOL = B * (W - 1) + 1  # physical pages, page 0 the sentinel
T = 5  # chunk-verify rows (gamma = 4) and packed-tree nodes
C = DEFAULT_PREFILL_CHUNK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases(dev):
    bf = lambda *shape: _sds(dev, shape)
    i32 = lambda *shape: _sds(dev, shape, jnp.int32)
    dense_kv = (bf(B, S, KVH, HD), bf(B, S, KVH, HD))
    paged_kv = (bf(POOL, PAGE, KVH, HD), bf(POOL, PAGE, KVH, HD))
    return {
        "decode": (decode_attention, (bf(B, H, HD), *dense_kv, i32(B))),
        "paged_decode": (
            paged_decode_attention,
            (bf(B, H, HD), *paged_kv, i32(B, W), i32(B)),
        ),
        "verify": (verify_attention, (bf(B, T, H, HD), *dense_kv, i32(B))),
        "paged_verify": (
            paged_verify_attention,
            (bf(B, T, H, HD), *paged_kv, i32(B, W), i32(B)),
        ),
        "tree_verify": (
            tree_verify_attention,
            (bf(B, T, H, HD), *dense_kv, i32(B), i32(B, T)),
        ),
        "paged_tree_verify": (
            paged_tree_verify_attention,
            (bf(B, T, H, HD), *paged_kv, i32(B, W), i32(B), i32(B, T)),
        ),
        "prefill": (
            prefill_attention,
            (bf(B, C, H, HD), *dense_kv, i32(B), i32(B)),
        ),
        "paged_prefill": (
            paged_prefill_attention,
            (bf(B, C, H, HD), *paged_kv, i32(B, W), i32(B), i32(B)),
        ),
    }


KERNELS = (
    "decode", "paged_decode", "verify", "paged_verify", "tree_verify",
    "paged_tree_verify", "prefill", "paged_prefill",
)


@pytest.fixture(scope="module")
def kernel_hlo(one_chip):
    """name -> the compiled HLO text of that kernel (compiled once)."""
    cache = {}

    def get(name):
        if name not in cache:
            fn, args = _kernel_cases(one_chip)[name]
            cache[name] = jax.jit(fn).lower(*args).compile().as_text()
        return cache[name]

    return get


@pytest.mark.parametrize("name", KERNELS)
def test_attention_kernel_compiles_for_v5e(name, kernel_hlo):
    assert "tpu_custom_call" in kernel_hlo(name)


@pytest.mark.parametrize("name", KERNELS)
def test_attention_kernel_ops_keep_the_kernel_name(name, kernel_hlo):
    """The device trace names a kernel's operations by their HLO
    instruction, ``<kernel>.<n>``; the benchmark's roofline readers find
    the kernels by that prefix (``paged_decode_attention``, ...)."""
    ops = [line.split("=", 1)[0].split()[-1].lstrip("%")
           for line in kernel_hlo(name).splitlines()
           if "custom_call_target=\"tpu_custom_call\"" in line]
    assert ops and all(op.startswith(f"{name}_attention.") for op in ops), ops


def test_flash_attention_compiles_for_v5e(one_chip):
    """The forward-only kernel ``ops.attention(impl="auto")`` picks on TPU
    (monolithic prefill): GQA heads pre-expanded, a 1024-token prompt."""
    q = _sds(one_chip, (1, H, 1024, HD))
    compiled = jax.jit(flash_attention).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssm_scan_compiles_for_v5e(one_chip):
    cfg = configs.get_config("falcon-mamba-7b")
    f32 = lambda *shape: _sds(one_chip, shape, jnp.float32)
    di, ds = cfg.d_inner, cfg.ssm_state
    args = (
        f32(1, SSM_CHUNK, di), f32(1, SSM_CHUNK, di), f32(1, SSM_CHUNK, ds),
        f32(1, SSM_CHUNK, ds), f32(di, ds), f32(1, di, ds),
    )
    compiled = jax.jit(ssm_scan_chunk).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_layer_train_step_compiles_for_v5e(topo):
    """Forward and backward (plus clip and AdamW) of one qwen3-width layer
    through the real train-step builder, on a one-chip mesh of the described
    topology: the loss path must differentiate on the chip."""
    cfg = dataclasses.replace(QWEN3, num_layers=1)
    mesh = Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(AxisType.Auto, AxisType.Auto),
    )
    tcfg = TrainConfig(fsdp=False, zero1=False, remat_policy="full")
    art = make_train_step(cfg, tcfg, mesh)
    shape = ShapeConfig("t", seq_len=256, global_batch=2, kind="train")
    compiled = art.jitted(donate=True).lower(
        art.abstract_state(), art.abstract_batch(shape)
    ).compile()
    # params + AdamW moments in f32 are the step's arguments
    n = cfg.param_count()
    assert compiled.memory_analysis().argument_size_in_bytes >= 12 * n


OLMO = configs.get_config("olmo-1b")


@pytest.mark.parametrize("name", ["paged_decode", "paged_prefill"])
def test_paged_kernels_compile_at_olmo_widths(name, one_chip):
    """The serving kernels at OLMo-1B's attention: 16 query and 16 KV
    heads of 128 (GQA group 1, so every query head reads its own KV)."""
    h, kvh, hd = OLMO.num_heads, OLMO.num_kv_heads, OLMO.resolved_head_dim
    bf = lambda *shape: _sds(one_chip, shape)
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    kv = (bf(POOL, PAGE, kvh, hd), bf(POOL, PAGE, kvh, hd))
    fn, args = {
        "paged_decode": (paged_decode_attention, (bf(B, h, hd), *kv, i32(B, W), i32(B))),
        "paged_prefill": (paged_prefill_attention,
                          (bf(B, C, h, hd), *kv, i32(B, W), i32(B), i32(B))),
    }[name]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("heads,kv_heads,w", [
    (QWEN3.num_heads, QWEN3.num_kv_heads, 129),
    (QWEN3.num_heads, QWEN3.num_kv_heads, 161),
    (OLMO.num_heads, OLMO.num_kv_heads, 129),
], ids=["qwen3-max_seq2048", "qwen3-max_seq2560", "olmo-max_seq2048"])
def test_paged_decode_compiles_at_cell_tables(heads, kv_heads, w, one_chip):
    """The decode kernel at the benchmark cells' own engines: 16 slots,
    16-token pages, a table of W-1 = 128 or 160 logical pages and a pool of
    every slot's pages; its double-buffered K/V blocks must fit the scoped
    VMEM and its dynamic block loop must lower."""
    slots, hd = 16, 128
    pool = slots * (w - 1) + 1
    bf = lambda *shape: _sds(one_chip, shape)
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    args = (bf(slots, heads, hd), bf(pool, PAGE, kv_heads, hd),
            bf(pool, PAGE, kv_heads, hd), i32(slots, w), i32(slots))
    hlo = jax.jit(paged_decode_attention).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _olmo_cell_engine() -> dict:
    """The engine block (``max_slots``, ``max_seq``) of the benchmark cell
    that serves OLMo-1B, from its traffic file."""
    import json
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    spec = json.loads((repo / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == "olmo-colloc-dp2tp2")
    traffic = json.loads(
        (repo / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return traffic["engine"]


def _olmo_pool_bytes(es: dict) -> int:
    """The K and V pools of an engine with the cell's slots and max_seq."""
    return (es["max_slots"] * es["max_seq"] * OLMO.num_layers * 2
            * OLMO.num_kv_heads * OLMO.resolved_head_dim * 2)


@pytest.mark.parametrize("program", ["decode_loop", "prefill_chunks"])
def test_olmo_engine_programs_update_the_pool_in_place(program, one_chip,
                                                       monkeypatch):
    """The engine's decode loop (k = 8) and chunked prefill at OLMo-1B's
    widths and the cell's slots, compiled for a described v5e with the
    Pallas kernels the chip runs: the donated KV pool is updated in place,
    so the programs' temporaries stay a small share of the pool instead of
    holding a copy of it."""
    from repro.kernels import ops
    from repro.models import transformer as TM
    from repro.serving.engine import _program, decode_loop

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # the chip's kernels
    es = _olmo_cell_engine()
    slots, max_seq = es["max_slots"], es["max_seq"]
    pps = -(-max_seq // PAGE)
    place = lambda x: _sds(one_chip, x.shape, x.dtype)
    params = jax.tree.map(place, jax.eval_shape(lambda: TM.cast_params(
        TM.init_params(OLMO, jax.random.PRNGKey(0)), jnp.bfloat16)))
    cache = jax.tree.map(place, jax.eval_shape(lambda: TM.init_paged_cache(
        OLMO, slots, slots * pps + 1, PAGE, pps, jnp.bfloat16)))
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    if program == "decode_loop":
        fn = jax.jit(_program(decode_loop, OLMO, max_seq=max_seq, k=8),
                     donate_argnames=("tokens", "cache"))
        args = (params, i32(slots), cache, i32(slots))
    else:
        fn = jax.jit(_program(TM.prefill_chunks_into_slots, OLMO),
                     donate_argnames=("cache",))
        args = (params, i32(slots, C), i32(slots), cache)
    mem = fn.lower(*args).compile().memory_analysis()
    pool = _olmo_pool_bytes(es)
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < pool / 4, mem.temp_size_in_bytes / 2**30


def test_olmo_train_step_on_2x2_leaves_chip0_room_to_serve(topo):
    """The whole OLMo-1B train step, FSDP + ZeRO-1 on the described
    ``v5e:2x2`` at the benchmark cell's seq 2048 x batch 4: per device, its
    arguments, outputs and temporaries, with the initial params the
    benchmark keeps through its checked steps, leave chip 0 room for the
    engine's bf16 copy and the cell's KV pool (16 GB chip, 15.75 GiB
    usable)."""
    mesh = Mesh(
        np.array(topo.devices).reshape(2, 2), ("data", "model"),
        axis_types=(AxisType.Auto, AxisType.Auto),
    )
    tcfg = TrainConfig(fsdp=True, zero1=True, remat_policy="full")
    art = make_train_step(OLMO, tcfg, mesh)
    shape = ShapeConfig("t", seq_len=2048, global_batch=4, kind="train")
    mem = art.jitted(donate=False).lower(
        art.abstract_state(), art.abstract_batch(shape)
    ).compile().memory_analysis()
    gib = 2**30
    n = OLMO.param_count()
    step = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    params0 = 4 * n / 4  # f32 params over four devices
    copy = 2 * n  # the engine's bf16 serving copy
    pool = _olmo_pool_bytes(_olmo_cell_engine())
    assert mem.argument_size_in_bytes >= 3 * 4 * n / 4  # params, mu, nu
    assert (step + params0 + copy + pool) / gib < 15.75, (step / gib, pool / gib)
