"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic and driver, every per-layer metric its reader, and
the command refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_names_and_units_are_well_formed():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert (ROOT / conf["file"]).is_file()
    traffic = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    assert (ROOT / "bench" / "drivers" / f"{traffic['generator']}.py").is_file()
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in m["workloads"] for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    m = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert (ROOT / "bench" / "metrics" / f"{metric}.py").is_file()
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
    for cell in m["workloads"]:
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"]


def test_configuration_files_state_their_cuts():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483711", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_to_run_without_a_tpu():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
