"""Pallas TPU kernels for the perf-critical compute layers.

The paper's contribution is scheduler-level (see ``repro.core``); these
kernels cover the model compute hot spots it schedules around:
  * flash_attention.py  -- blocked online-softmax attention (MXU-tiled,
                           forward only)
  * decode_attention.py -- flash-decode: single-token ragged-batch decode
                           attention over the KV cache (serving hot path)
  * verify_attention.py -- chunk-verify: flash-decode generalized to the
                           gamma+1 query chunk of speculative decoding
  * tree_verify / prefill_attention.py -- ancestor-masked and ragged
                           chunked-prefill generalizations of chunk-verify
  * paged_*.py          -- the same four over the block-table page pool
  * ssm_scan.py         -- Mamba1 selective scan with VMEM-resident state
ops.py dispatches between Pallas and XLA fallbacks; ref.py holds the
pure-jnp oracles used by the test suite.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
