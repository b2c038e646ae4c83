from repro.kernels.hash_aggregate.ops import hash_aggregate
