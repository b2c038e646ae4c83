"""The paper's placement and allocator axes as enums.

Memory placement policy (Section 3.3), thread placement (Section 3.2, as a
mesh layout) and allocator selection (Section 3.1) are plain enums, so that
they are hashable (usable as jit static arguments and plan-cache keys) and
name their choice in a configuration file.
"""
from __future__ import annotations

import enum


# ---------------------------------------------------------------------------
# Paper axis 3: memory placement policies (Section 3.3 of the paper)
# ---------------------------------------------------------------------------
class PlacementPolicy(enum.Enum):
    """NUMA memory-placement policies mapped to mesh shardings.

    FIRST_TOUCH  state is owned by the shard group that produced it and is
                 replicated along the data axis (the OS-default analogue).
    INTERLEAVE   state is sharded round-robin across every device in the mesh
                 (the paper's winner for shared state).
    LOCAL_ALLOC  state is private to each consuming shard; no shared copy.
    PREFERRED    state pinned to one submesh slice (``preferred_index``).
    """

    FIRST_TOUCH = "first_touch"
    INTERLEAVE = "interleave"
    LOCAL_ALLOC = "local_alloc"
    PREFERRED = "preferred"


# ---------------------------------------------------------------------------
# Paper axis 2: thread placement (Section 3.2) -> logical-to-physical layout
# ---------------------------------------------------------------------------
class MeshLayout(enum.Enum):
    """How logical mesh axes map onto the physical torus.

    NONE    device enumeration order (the "OS free to migrate" baseline).
    SPARSE  model-parallel groups spread across distinct ICI neighbourhoods,
            maximizing aggregate link bandwidth (paper's Sparse affinity).
    DENSE   model-parallel groups packed into adjacent chips, minimizing hop
            count inside a group (paper's Dense affinity).
    """

    NONE = "none"
    SPARSE = "sparse"
    DENSE = "dense"


# ---------------------------------------------------------------------------
# Paper axis 1: allocator selection (Section 3.1)
# ---------------------------------------------------------------------------
class AllocatorKind(enum.Enum):
    BUMP = "bump"          # ptmalloc analogue: one global region, one lock
    ARENA = "arena"        # jemalloc analogue: per-stream arenas, round robin
    SLAB = "slab"          # tbbmalloc/tcmalloc analogue: size-class slabs
    HOARD = "hoard"        # Hoard analogue: global heap + per-stream heaps
