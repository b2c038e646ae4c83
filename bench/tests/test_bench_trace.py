"""The reduction from a profiler trace to device metrics, on hand-made
events and on a small trace recorded on a TPU v5e chip
(``bench/fixtures/trace_v5e.json.gz``)."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402

FIXTURE = ROOT / "bench" / "fixtures" / "trace_v5e.json.gz"


def test_union_merges_and_clips():
    assert devtrace.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [
        (1, 4), (5, 10)]
    assert devtrace.union([(0, 1)], 2, 5) == []


def test_gaps_complement_the_busy_intervals():
    assert devtrace.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6),
                                                      (7, 10)]
    assert devtrace.gaps([], 0, 3) == [(0, 3)]


def test_reduce_busy_collectives_ops_and_gaps():
    ms = 1_000_000
    trace = {
        "window": [0, 100 * ms],
        "devices": {
            "/device:TPU:0": [["fusion.1", 0, 40 * ms],
                              ["all-to-all.3", 30 * ms, 20 * ms],
                              ["sort.2", 90 * ms, 20 * ms]],   # cut at 100
            "/device:TPU:1": [["fusion.7", 10 * ms, 20 * ms]],
        },
        "host": [["dispatch", 55 * ms, 10 * ms],
                 ["wait", 50 * ms, 40 * ms],
                 ["window-wide", 0, 100 * ms]],
    }
    out = devtrace.reduce(trace)
    assert out["window_s"] == pytest.approx(0.1)
    # chip 0 busy 0-50 and 90-100 ms = 60 ms, chip 1 20 ms: mean 40 ms
    assert out["per_device"]["/device:TPU:0"]["busy_s"] == pytest.approx(.06)
    assert out["busy_s"] == pytest.approx(0.04)
    assert out["collective_s"] == pytest.approx(0.01)     # (20 + 0) / 2
    # each op by its own name, clipped to the window, averaged over chips
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.02)]
    # no chip busy in 50-90 ms: "wait" overlaps it most
    assert out["idle_gaps"] == [["wait (x1)", pytest.approx(0.04)]]


def test_short_gaps_are_counted_not_named():
    us = 1000
    trace = {"window": [0, 1000 * us],
             "devices": {"/device:TPU:0": [["a.1", 0, 500 * us],
                                           ["a.2", 510 * us, 490 * us]]},
             "host": [["busy host", 0, 1000 * us]]}
    out = devtrace.reduce(trace)
    assert out["idle_gaps"] == [["gaps under 100 us (x1)",
                                 pytest.approx(10e-6)]]


def test_op_label_keeps_instruction_opcode_and_shape_only():
    label = devtrace.op_label
    assert label("%fusion.22 = f32[128]{0:T(1024)} fusion(f32[64]{0} "
                 "%all-to-all.3), kind=kCustom") == "fusion.22: fusion -> f32[128]"
    assert label('%c.3 = (f32[8,128]{1,0:T(8,128)}, s32[8]{0}) custom-call('
                 's32[8]{0} %p), custom_call_target="tpu_custom_call"') == (
        "c.3: custom-call tpu_custom_call -> (f32[8,128], s32[8])")
    assert label("ThreadpoolListener::Run") == "ThreadpoolListener::Run"
    # a fusion that only reads a collective's result is no collective
    ops = [[label("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %all-gather.2)"),
            0, 10], [label("%all-gather.2 = f32[4]{0} all-gather(f32[1]{0} "
                           "%p)"), 10, 5]]
    out = devtrace.reduce({"window": [0, 20], "devices": {"/device:TPU:0": ops},
                           "host": []})
    assert out["collective_s"] == pytest.approx(5e-9)


def test_recorded_trace_reduces_to_its_busy_time_and_gaps():
    """200 ms of a window of ``tpch_sf1.join`` on one v5e chip: two plans
    back to back, the gap between them spent in the host's ReadSyncFlag."""
    with gzip.open(FIXTURE, "rt") as f:
        trace = json.load(f)
    out = devtrace.reduce(trace)
    ops = trace["devices"]["/device:TPU:0"]
    lo, hi = trace["window"]
    # busy time by a plain sweep over the window's nanoseconds
    covered = set()
    for _, s, d in ops:
        covered.update(range(max(lo, s) // 1000, min(hi, s + d) // 1000))
    assert out["busy_s"] == pytest.approx(len(covered) * 1e-6, abs=2e-5)
    assert out["busy_s"] == pytest.approx(0.197048886)
    assert out["window_s"] == pytest.approx(0.2)
    assert out["collective_s"] == 0.0
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert out["idle_gaps"][0] == ["ReadSyncFlag (x1)",
                                   pytest.approx(0.00295025)]
    assert len(out["device_ops"]) == 10
    assert out["device_ops"][0][0] == "fusion.13: fusion -> f32[3000000]"
    assert sum(s for _, s in out["device_ops"]) <= out["busy_s"]
