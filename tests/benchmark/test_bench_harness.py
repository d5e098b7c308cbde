"""BENCHMARK.json against what the harness finds by name, and the command's
refusals: no result without a TPU, or without the program beside it."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_every_name_finds_its_files():
    b = _bench()
    for c in b["configs"]:
        assert NAME.match(c["name"])
        with open(ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"])
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        assert (CHIP / "limits" / f"{w['name']}.json").is_file()
    names = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e and set(m.get("workloads", names)) <= names
    assert "setup_s" in e2e


def test_serve_as_matches_the_published_keys():
    from chip import run as harness
    for c in _bench()["configs"]:
        with open(ROOT / c["file"]) as f:
            harness.model_config(json.load(f))


def _run(cwd, *, env):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "qwen3-4b.chat-topp",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(ROOT, env=env)
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    assert "no TPU" in r.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in _bench()["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0 and not r.stdout.strip()
