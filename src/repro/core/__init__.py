"""Core: placement policies, mesh layouts, the topology model.

This package holds the paper's primary contribution adapted to TPU: the
placement system (memory placement policies, mesh layouts as the
thread-placement analogue, allocator kinds) that the analytics engine's
operators run under without code changes.
"""
from repro.core.config import AllocatorKind, MeshLayout, PlacementPolicy
from repro.core.topology import ICI_LINK_BW, TorusTopology
