"""Device-arena memory management: allocators and their microbenchmark."""
from repro.memory.allocators import (Allocator, AllocStats, Block,
                                     make_allocator)
from repro.memory.microbench import MicrobenchResult, run_microbench, sweep
