"""Pallas TPU kernels for the analytics engine's hot spots.

Each kernel package ships three layers:
  kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     jit'd public op with mode dispatch (pallas | interpret | ref)
  ref.py     pure-jnp oracle (test ground truth + CPU lowering path)

Kernels:
  hash_aggregate   partitioned distributive aggregation (W2 hot loop)
  join_probe       partition-wise broadcast-compare probe (W3/W4 hot loop)
  radix_partition  radix histogram pass (analytics W1-W4 partitioner)
"""
from repro.kernels.hash_aggregate import hash_aggregate
from repro.kernels.join_probe import join_probe
from repro.kernels.radix_partition import block_histograms, radix_partition
