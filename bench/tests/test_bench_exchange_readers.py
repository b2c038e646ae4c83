"""The readers of the four-chip cell's per-layer metrics, on hand-built run
views: a four-chip trace with unequal busy time and a known collective
time, and program spans that carry the Exchanges' wire bytes. Each reader
gives nothing where what it reads is absent."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

CELL = "tpch_sf1_x4.join"
READERS = ("exchange.collective_share", "exchange.wire_mb_per_query",
           "device.busy_imbalance")


@pytest.fixture
def program_tracer():
    """The program's tracer, empty before and after the test."""
    from repro.analytics import tracing
    tracing.tracer().clear()
    yield tracing.tracer()
    tracing.tracer().clear()


def _view(completed, trace=None):
    return harness.RunView(harness.load_cell(CELL), 30.0, completed, 0,
                           trace, {}, {}, {}, {})


def _request(rid, query="q3"):
    return harness.Request(0, query, 0.0, 1.0, {}, {"execute": 1.0}, None,
                           rid)


def _four_chip_trace():
    busy = {"/device:TPU:0": 12.0, "/device:TPU:1": 8.0,
            "/device:TPU:2": 8.0, "/device:TPU:3": 8.0}
    coll = {"/device:TPU:0": 1.0, "/device:TPU:1": 3.0,
            "/device:TPU:2": 3.0, "/device:TPU:3": 3.0}
    return {"window_s": 30.0, "busy_s": 9.0, "collective_s": 2.5,
            "per_device": {d: {"busy_s": busy[d], "collective_s": coll[d]}
                           for d in busy},
            "device_ops": [], "idle_gaps": []}


@pytest.mark.parametrize("name", READERS)
def test_cell_lists_the_reader(name):
    assert name in {m["name"] for m in harness.load_cell(CELL).per_layer}


def test_collective_share_is_collective_over_busy_time():
    read = harness.metric_reader("exchange.collective_share")
    assert read(_view([], _four_chip_trace())) == pytest.approx(
        100.0 * 2.5 / 9.0)


def test_busy_imbalance_is_the_busiest_chip_over_the_mean():
    read = harness.metric_reader("device.busy_imbalance")
    # chip 0 works 12 s against a mean of 9 s
    assert read(_view([], _four_chip_trace())) == pytest.approx(
        100.0 * (12.0 / 9.0 - 1.0))
    even = _four_chip_trace()
    for d in even["per_device"].values():
        d["busy_s"] = 9.0
    assert read(_view([], even)) == pytest.approx(0.0)


def test_wire_mb_per_query_sums_a_requests_dispatches(program_tracer):
    tr = program_tracer
    tr.add_complete("plan.dispatch", "plan", 1.0, 1.001, trace_id=1,
                    exchange_bytes=30_000_000, exchanges=4)
    tr.add_complete("plan.dispatch", "plan", 1.1, 1.102, trace_id=1,
                    exchange_bytes=25_803_120, exchanges=4)
    tr.add_complete("plan.dispatch", "plan", 1.6, 1.604, trace_id=2,
                    exchange_bytes=24_395_028, exchanges=5)
    tr.add_complete("plan.dispatch", "plan", 1.7, 1.701, trace_id=9,
                    exchange_bytes=10**9, exchanges=1)    # not completed
    read = harness.metric_reader("exchange.wire_mb_per_query")
    # request 5 (a deduplicated peer) dispatched nothing of its own
    view = _view([_request(1), _request(2, "q5"), _request(5)])
    assert read(view) == pytest.approx(
        (55_803_120 + 24_395_028) / 2 / 1e6)


def test_wire_mb_per_query_gives_nothing_without_the_count(program_tracer):
    read = harness.metric_reader("exchange.wire_mb_per_query")
    view = _view([_request(1)])
    assert read(view) is None                          # no spans at all
    # a program whose dispatch spans carry no wire count
    program_tracer.add_complete("plan.dispatch", "plan", 1.0, 1.001,
                                trace_id=1, plan="q3")
    assert read(view) is None


@pytest.mark.parametrize("name", ["exchange.collective_share",
                                  "device.busy_imbalance"])
def test_trace_readers_give_nothing_without_a_trace(name):
    read = harness.metric_reader(name)
    assert read(_view([], None)) is None
    idle = dict(_four_chip_trace(), busy_s=0.0, collective_s=0.0,
                per_device={d: {"busy_s": 0.0, "collective_s": 0.0}
                            for d in ("/device:TPU:0", "/device:TPU:1")})
    assert read(_view([], idle)) is None


def test_busy_imbalance_needs_two_chips():
    read = harness.metric_reader("device.busy_imbalance")
    one = dict(_four_chip_trace(),
               per_device={"/device:TPU:0": {"busy_s": 9.0,
                                             "collective_s": 0.0}})
    assert read(_view([], one)) is None
