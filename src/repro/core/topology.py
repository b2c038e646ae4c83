"""NUMA-distance model for TPU torus topologies.

The paper characterizes its machines by *relative NUMA node memory latency*
(Table 3: local 1.0, 1-hop 1.2 ... 3-hop 1.6 on machine A) and interconnect
bandwidth. The TPU analogue: chips in a pod form a 2D torus connected by ICI
links; pods are bridged by a much slower inter-pod tier (DCI). This module is
the framework's cost model for "remote memory access": given a logical mesh
layout it prices each collective in hop-weighted bytes, which is how the
SPARSE/DENSE/NONE thread-placement analogues are compared quantitatively
(the CPU-backend HLO is placement-agnostic, so this model supplies the
topology term the hardware would).

Hardware constants (TPU v5e):
  ICI link bandwidth  ~50 GB/s / link  (4 links/chip on a 2D torus)
  inter-pod (DCI)     modeled at 1/8 of an ICI link per chip pair
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

ICI_LINK_BW = 50e9                # bytes/s per link
DCI_BW = ICI_LINK_BW / 8          # inter-pod tier


@dataclass(frozen=True)
class TorusCoord:
    pod: int
    x: int
    y: int


@dataclass(frozen=True)
class TorusTopology:
    """``n_pods`` pods, each an ``xdim`` x ``ydim`` wrap-around torus."""

    n_pods: int = 1
    xdim: int = 16
    ydim: int = 16

    @property
    def chips_per_pod(self) -> int:
        return self.xdim * self.ydim

    @property
    def n_chips(self) -> int:
        return self.n_pods * self.chips_per_pod

    def coord(self, device_index: int) -> TorusCoord:
        pod, rem = divmod(device_index, self.chips_per_pod)
        x, y = divmod(rem, self.ydim)
        return TorusCoord(pod=pod, x=x, y=y)

    def hop_distance(self, a: int, b: int) -> float:
        """Torus manhattan distance; cross-pod hops carry a DCI penalty."""
        ca, cb = self.coord(a), self.coord(b)
        dx = min(abs(ca.x - cb.x), self.xdim - abs(ca.x - cb.x))
        dy = min(abs(ca.y - cb.y), self.ydim - abs(ca.y - cb.y))
        pod_penalty = 0.0
        if ca.pod != cb.pod:
            # crossing DCI costs at least a full pod traverse in hop
            # equivalents (bandwidth tier is 8x slower per topology spec)
            pod_penalty = self.xdim + self.ydim
        return dx + dy + pod_penalty

    # -- relative latency table, mirroring paper Table 3 -------------------
    def relative_latency(self, a: int, b: int) -> float:
        """Paper-style relative access latency (local = 1.0)."""
        d = self.hop_distance(a, b)
        return 1.0 + 0.2 * d


# ---------------------------------------------------------------------------
# Collective cost model
# ---------------------------------------------------------------------------
def ring_neighbor_hops(topo: TorusTopology, ring: Sequence[int]) -> float:
    """Mean torus hop distance between successive ring members.

    1.0 means the logical ring is a physical ring (each transfer is one ICI
    hop); larger values mean each ring step crosses multiple links and thus
    divides effective bandwidth.
    """
    n = len(ring)
    if n <= 1:
        return 0.0
    total = 0.0
    for i in range(n):
        total += topo.hop_distance(ring[i], ring[(i + 1) % n])
    return total / n


def ring_allreduce_seconds(nbytes: int, group: Sequence[int],
                           topo: TorusTopology) -> float:
    """Bidirectional-ring all-reduce: 2*(n-1)/n of the buffer crosses each
    link; hop dilution divides effective bandwidth."""
    n = len(group)
    if n <= 1:
        return 0.0
    hops = max(1.0, ring_neighbor_hops(topo, group))
    # two directions usable on a torus ring -> 2 links
    eff_bw = 2 * ICI_LINK_BW / hops
    return 2.0 * nbytes * (n - 1) / n / eff_bw


def all_gather_seconds(nbytes: int, group: Sequence[int],
                       topo: TorusTopology) -> float:
    n = len(group)
    if n <= 1:
        return 0.0
    hops = max(1.0, ring_neighbor_hops(topo, group))
    eff_bw = 2 * ICI_LINK_BW / hops
    return nbytes * (n - 1) / n / eff_bw


def all_to_all_seconds(nbytes: int, group: Sequence[int],
                       topo: TorusTopology) -> float:
    """All-to-all moves (n-1)/n of the buffer, but bisection-limited."""
    n = len(group)
    if n <= 1:
        return 0.0
    hops = max(1.0, ring_neighbor_hops(topo, group))
    # bisection of a ring of n chips with 2 links each
    eff_bw = 4 * ICI_LINK_BW / hops
    return nbytes * (n - 1) / n / eff_bw


COLLECTIVE_COSTS = {
    "all-reduce": ring_allreduce_seconds,
    "all-gather": all_gather_seconds,
    "reduce-scatter": all_gather_seconds,   # same wire bytes as all-gather
    "all-to-all": all_to_all_seconds,
    "collective-permute": lambda nbytes, group, topo: (
        nbytes / (ICI_LINK_BW * max(1.0, 1.0 / max(1.0, ring_neighbor_hops(topo, group))))
        if len(group) > 1 else 0.0),
}

