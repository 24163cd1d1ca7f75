"""BENCHMARK.json and the files it names: every configuration, traffic mix
and metric is found by name and loads, and the file keeps to the
benchmark's contract."""
import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from sartbench.spec import (BENCH, ROOT, load_cell, load_config,  # noqa: E402
                            load_reader, load_traffic)

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]


@pytest.mark.parametrize("path", sorted(
    p.stem for p in (BENCH / "configs").glob("*.json")))
def test_every_config_file_builds_the_model(path):
    from repro.models import ModelConfig
    conf = load_config(path)
    cfg = ModelConfig(**conf["model"])
    assert cfg.num_heads % cfg.num_kv_heads == 0
    e = conf["engine"]
    assert e["eos_id"] >= cfg.vocab_size or e["eos_id"] < 0
    assert e["temperature"] == 0.0      # the check needs greedy tokens
    assert set(conf["check"]) == {"token_gap", "reward_gap"}
    assert (BENCH / "configs" / f"{conf['reference']}.py").is_file()


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (BENCH / "metrics").glob("*.py")))
def test_every_metric_reader_loads(name):
    assert callable(load_reader(name).read)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_and_reports_enough(w):
    cell = load_cell(w["name"])
    assert cell.chips == w["chips"] == 1
    e2e = [m.name for m in cell.metrics_of("end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.metrics_of("per_layer")
    assert load_traffic(w["traffic"])["n"] >= load_traffic(w["traffic"])["m"]


def test_names_units_and_uniqueness():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    for w in SPEC["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]].get("workloads", cells)
        for cell in m.get("workloads", cells):
            assert cell in moved, (m["name"], cell)
        layers.setdefault(m["layer"], []).append(m["name"])
    if any(m["unit"] == "%" and "roofline" in m["name"]
           for m in SPEC["per_layer"]):
        assert any("mfu" in m["name"] for m in SPEC["per_layer"])


def test_run_seconds_fits_a_full_check():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert 1 <= SPEC["run_seconds"] <= 51 and total <= 43200


def test_command_refuses_a_tree_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and bench/, the
    command exits non-zero and prints no result."""
    import shutil
    import subprocess
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    cell = SPEC["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
