"""The entry point has no CPU branch: without a TPU it prints no result
and exits non-zero."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from lib.harness import kind_module

ROOT = Path(__file__).resolve().parents[2]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell["name"],
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_names_files_under_its_paths():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_each_mix_kind_names_the_module_that_drives_it():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        mix = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        mod = kind_module(mix["kind"])
        assert mod.__name__ == f"lib.{mix['kind']}"
        assert callable(mod.run_cell)


def test_unknown_kind_has_no_module():
    for kind in ("no_such_kind", "../run", "stats"):
        with pytest.raises(KeyError):
            kind_module(kind)
