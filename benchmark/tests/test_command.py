"""The command itself: what it refuses, and that the files it names exist."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def run(*args, cwd=ROOT, env=None):
    return subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=cwd,
                          env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
                          capture_output=True, text=True, timeout=300)


def test_without_an_accelerator_no_result_and_a_nonzero_exit():
    cell = SPEC["workloads"][0]["name"]
    out = run("--workload", cell, "--seed", "2300000011", "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


def test_an_unknown_workload_is_refused():
    out = run("--workload", "nope", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_every_name_in_benchmark_json_finds_its_files():
    paths = SPEC["paths"]
    for cfg in SPEC["configs"]:
        assert any(cfg["file"].startswith(p + "/") for p in paths)
        body = json.loads((ROOT / cfg["file"]).read_text())
        assert "reduced" in body and "assumed" in body and "_source" in body
        assert body["reduced"] == cfg["reduced"]
    for cell in SPEC["workloads"]:
        assert (ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json").exists()
        params = json.loads((ROOT / "benchmark" / "cells" / f"{cell['name']}.json").read_text())
        assert params["limits"]
        mix = json.loads((ROOT / "benchmark" / "traffic" / f"{cell['traffic']}.json").read_text())
        cfg = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
        assert mix["runner"] in json.loads((ROOT / cfg["file"]).read_text())["runners"]
        assert (ROOT / "benchmark" / "runners" / f"{mix['runner']}.py").exists()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").exists(), m["name"]


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric():
    for cell in SPEC["workloads"]:
        def mine(kind):
            return [m["name"] for m in SPEC[kind]
                    if "workloads" not in m or cell["name"] in m["workloads"]]
        e2e, layers = mine("end_to_end"), mine("per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layers
        for m in SPEC["per_layer"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                assert m["moves"] in e2e


@pytest.mark.parametrize("reader,obs", [
    ("flash_fwd_roofline.train", {"kind": "train"}),
    ("decode_roofline.serve", {"kind": "serve"}),
    ("collective_exposed_share.train4", {"kind": "train"}),
    ("device_idle_share.serve", {"kind": "serve"}),
    ("mfu.serve", {"kind": "serve", "prompt_len_in_window": []}),
])
def test_a_reader_with_nothing_to_read_returns_nothing(reader, obs):
    from benchmark import harness

    assert harness.load_reader(reader)(obs) is None
