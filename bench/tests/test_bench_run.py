"""Whole runs of the harness at a tiny scale on the CPU, the look for a chip
skipped: a sound run is correct, and a run whose timed path is broken
underneath is not, for each fault the cells can have: an answer altered
where it is produced, half of the rows left out, an answer that never comes,
and (on four devices) the exchange between chips left out."""
import itertools
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SCALE, SECONDS = 0.002, 1.0


def run(cell_name: str, seed: int):
    import jax
    cell = harness.load_cell(cell_name)
    cell.config["scale"] = SCALE
    return harness.run_cell(cell, seed, SECONDS, False, jax.devices(),
                            harness.peaks("TPU v5 lite"), time.perf_counter())


@pytest.mark.parametrize("cell", ["tpch_sf1.join", "tpch_sf10.scan"])
def test_sound_run_is_correct(cell):
    out = run(cell, 2**31 + 3)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"qps", "setup_s"}
    assert out["metrics"]["qps"]["value"] > 0


@pytest.mark.parametrize("cell", ["tpch_sf1.join", "tpch_sf10.scan"])
def test_altered_answer_is_not_correct(cell, monkeypatch):
    from repro.analytics import planner
    call = planner.CompiledPlan.__call__

    def altered(self, tables):
        out = dict(call(self, tables))
        k = next(k for k, v in out.items()
                 if not k.startswith("_") and np.asarray(v).dtype.kind == "f")
        out[k] = out[k] * 1.001 + 1.0
        return out

    monkeypatch.setattr(planner.CompiledPlan, "__call__", altered)
    out = run(cell, 2**31 + 4)
    assert not out["correct"]
    assert out["checks"]["rel_err"]["value"] > out["checks"]["rel_err"]["limit"]


@pytest.mark.parametrize("cell", ["tpch_sf1.join", "tpch_sf10.scan"])
def test_half_of_the_rows_left_out_is_not_correct(cell, monkeypatch):
    """The plans answer over the first half of every table's rows."""
    from repro.analytics import planner
    call = planner.CompiledPlan.__call__

    def half(self, tables):
        return call(self, {t: {c: v[:len(v) // 2] for c, v in cols.items()}
                           for t, cols in tables.items()})

    monkeypatch.setattr(planner.CompiledPlan, "__call__", half)
    out = run(cell, 2**31 + 8)
    assert not out["correct"]


def test_qps_counts_whole_answers_up_to_the_last_one_in_the_window():
    def req(t_done, value=True, error=None):
        return harness.Request(0, "q1", 0.0, t_done, {} if value else None,
                               {"execute": 1.0}, error)

    win = harness.Window(100.0, 110.0, [
        req(102.0), req(105.0), req(108.0),
        req(109.0, value=False),                # failed: not counted
        req(109.5, error="shed"),               # answered with an error
        req(111.0)])                            # after the close
    assert win.qps() == 3 / 8.0
    assert harness.Window(0.0, 1.0, [req(2.0)]).qps() == 0.0


def test_a_traffic_loop_without_its_file_is_refused(monkeypatch):
    with pytest.raises(ValueError, match="bench/loops/open"):
        harness.loop_module("open")
    spec = harness.benchmark()
    cell = harness.load_cell(spec["workloads"][0]["name"])
    assert callable(harness.loop_module(cell.traffic["loop"]).run)
    real = harness._json

    def open_loop(path):
        d = real(path)
        return dict(d, loop="open") if path.parent.name == "traffic" else d

    monkeypatch.setattr(harness, "_json", open_loop)
    with pytest.raises(ValueError, match="bench/loops/open"):
        harness.load_cell(cell.name)


def test_config_values_convert_by_field_type():
    from repro.analytics.service.scheduler import ThreadPlacement
    from repro.core.config import PlacementPolicy
    ctx = harness.context({"context": {"executor": "cost",
                                       "policy": "INTERLEAVE"}}, [])
    assert ctx.policy is PlacementPolicy.INTERLEAVE and ctx.mesh is None
    svc = harness.service_config({"service": {
        "n_pools": 2, "placement": ThreadPlacement.OS_DEFAULT.name}})
    assert svc.placement is ThreadPlacement.OS_DEFAULT and svc.n_pools == 2
    with pytest.raises(KeyError):
        harness.context({"context": {"no_such_field": 1}}, [])


def test_streams_send_every_query_once_a_pass_in_a_fixed_order():
    cell = harness.load_cell("tpch_sf1.join")
    for s in range(len(cell.streams)):
        a = list(itertools.islice(harness.stream_order(cell.traffic, s), 30))
        b = list(itertools.islice(harness.stream_order(cell.traffic, s), 30))
        assert a == b
        passes = [a[i:i + 3] for i in range(0, 30, 3)]
        assert all(sorted(p) == ["q18", "q3", "q5"] for p in passes)
        assert len({tuple(p) for p in passes}) > 1


def test_answer_that_never_comes_is_not_correct(monkeypatch):
    from repro.analytics import planner
    call = planner.CompiledPlan.__call__
    broken = {"on": False}

    def failing(self, tables):
        if broken["on"]:
            raise RuntimeError("injected: the plan never answers")
        return call(self, tables)

    window = harness.run_window

    def window_with_fault(*a, **kw):
        broken["on"] = True
        return window(*a, **kw)

    monkeypatch.setattr(planner.CompiledPlan, "__call__", failing)
    monkeypatch.setattr(harness, "run_window", window_with_fault)
    out = run("tpch_sf1.join", 2**31 + 5)
    assert not out["correct"]
    assert out["failed"] == out["attempted"] > 0
    assert out["checks"]["missing"]["value"] == out["attempted"]


FOUR_DEVICES = """
import sys, time, jax
sys.path[:0] = [{root!r}, {src!r}]
from bench import checks, harness
from repro.analytics import planner

# the four-chip configuration under the join mix, held to the one-chip join
# cell's limits (the same queries and comparison)
spec = harness.benchmark()
spec["configs"].append({{"name": "tpch_sf1_x4",
                         "file": "bench/configs/tpch_sf1_x4.json"}})
spec["workloads"].append({{"name": "tpch_sf1_x4.join", "config": "tpch_sf1_x4",
                           "traffic": "join", "chips": 4}})
limits = checks.limits
checks.limits = lambda workload: limits("tpch_sf1.join")

def run(seed):
    cell = harness.load_cell("tpch_sf1_x4.join", spec)
    cell.config["scale"] = {scale!r}
    return harness.run_cell(cell, seed, {seconds!r}, False, jax.devices(),
                            harness.peaks("TPU v5 lite"), time.perf_counter())

sound = run(2**31 + 6)
exchange = planner._DistributedExecutor._exchange
def left_out(self, node):
    if node.kind in ("broadcast", "hash"):
        return self.run(node.child)      # rows stay on the chip they are on
    return exchange(self, node)
planner._DistributedExecutor._exchange = left_out
broken = run(2**31 + 7)
print("RESULT", sound["correct"], broken["correct"],
      sound["device"]["count"])
"""


def test_exchange_left_out_is_not_correct_on_four_devices():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    code = FOUR_DEVICES.format(root=str(ROOT), src=str(ROOT / "src"),
                               scale=SCALE, seconds=SECONDS)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")]
    assert line == ["RESULT True False 4"], proc.stdout[-2000:]
