"""Every file that BENCHMARK.json names loads, and the benchmark refuses a
machine without the chip it needs before doing any work."""
import importlib
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import checks, harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / SPEC["command"][1]).is_file()
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert 2 * sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        2, len(CELLS))


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_loads(config):
    cfg = json.loads((ROOT / config["file"]).read_text())
    assert cfg["name"] == config["name"]
    assert cfg["reduced"] == config["reduced"]
    assert cfg["scale"] > 0 and cfg["chips"] in (1, 4)
    assert set(cfg["service"]) <= {"n_pools", "workers_per_pool"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traffic_and_limits_load(cell):
    c = harness.load_cell(cell)
    assert c.streams and c.traffic["queries"]
    for s, q in c.plan_keys():
        mod = checks.reference_module(q)
        assert callable(mod.answer) and isinstance(mod.READS, dict)
    plans = harness.build_plans(c)
    assert len(set(plans.values())) == len(plans)   # no two streams merge
    assert set(checks.limits(cell)) == set(checks.NAMES)
    assert c.config["chips"] == c.chips


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_loads(metric):
    assert callable(harness.metric_reader(metric["name"]))


def test_reference_modules_import_nothing_of_the_program():
    for path in sorted((ROOT / "bench" / "reference").glob("*.py")):
        assert "repro" not in path.read_text(), path.name
        importlib.import_module(f"bench.reference.{path.stem}"
                                if path.stem != "__init__"
                                else "bench.reference")


def test_peaks_table_refuses_unknown_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.DeviceError):
        harness.peaks("TPU v9 imaginary")


def test_run_refuses_a_machine_without_tpu(capsys):
    run = importlib.import_module("bench.run")
    assert run.main(["--workload", CELLS[0], "--seed", "3",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
