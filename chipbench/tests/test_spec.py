"""BENCHMARK.json names files that exist, and the command refuses a
machine without a TPU."""

import json
import os
import subprocess
import sys

from chipbench import harness

ROOT = harness.ROOT


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_has_its_file():
    b = bench()
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"], b)
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


def test_programs_entries_match_the_config_files():
    for c in bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        mc = harness.importlib.import_module(
            f"chipbench.adapters.{cfg['reference']}").program_config(cfg)
        assert mc.n_layers == cfg["num_hidden_layers"]
        assert mc.d_model == cfg["hidden_size"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "ds7b.short", "--seed", "1", "--seconds", "10",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr
