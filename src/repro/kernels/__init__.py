"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships three layers:
  kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     jit'd public op with mode dispatch (pallas | interpret | ref)
  ref.py     pure-jnp oracle (test ground truth + CPU lowering path)

Kernels:
  flash_attention  train/prefill attention (GQA + causal + local window)
  rglru_scan       RG-LRU linear recurrence (chunked Hillis-Steele)
  rwkv6_scan       RWKV6 WKV recurrence (VMEM-resident per-head state)
  radix_partition  radix histogram pass (analytics W1-W4 partitioner)
  hash_aggregate   partitioned distributive aggregation (W2 hot loop)
  join_probe       partition-wise broadcast-compare probe (W3/W4 hot loop)
"""
from repro.kernels.flash_attention import decode_attention, flash_attention
from repro.kernels.hash_aggregate import hash_aggregate
from repro.kernels.join_probe import join_probe
from repro.kernels.radix_partition import block_histograms, radix_partition
from repro.kernels.rglru_scan import linear_scan
from repro.kernels.rwkv6_scan import wkv6, wkv6_step
