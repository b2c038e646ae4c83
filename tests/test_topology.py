"""The torus topology model and the mesh layouts' ring hops."""
from repro.core.topology import TorusTopology
from repro.core.meshes import layout_report


def test_layout_hops():
    """NONE (OS-default analogue) must dilate ring hops; affinitized
    layouts ride physical rings (paper Fig 3/Table 2)."""
    rep = layout_report(TorusTopology(n_pods=1))
    assert rep["sparse"]["data"] == 1.0
    assert rep["dense"]["model"] == 1.0
    assert rep["none"]["data"] > 4.0
    assert rep["none"]["model"] > 4.0


def test_relative_latency_table():
    """Mirrors the paper's Table 3 latency tiers (local < 1 hop < 2 hop)."""
    topo = TorusTopology(n_pods=2)
    assert topo.relative_latency(0, 0) == 1.0
    near = topo.relative_latency(0, 1)
    far = topo.relative_latency(0, 8 * 16 + 8)   # across the pod
    cross = topo.relative_latency(0, topo.chips_per_pod)  # other pod
    assert 1.0 < near < far < cross
