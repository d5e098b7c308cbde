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
        assert (CHIP / "reference" / f"{cfg['reference']}.py").is_file()
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


def _config(name):
    with open(CHIP / "configs" / f"{name}.json") as f:
        return json.load(f)


# The program's configuration of each committed file, as the harness built
# it before serve_as was read generically; every other field is the default.
PINNED = {
    "qwen3-4b": dict(
        name="qwen3-4b", family="decoder", n_layers=36, d_model=2560,
        n_heads=32, n_kv_heads=8, d_ff=9728, vocab_size=151936, head_dim=128,
        qk_norm=True, rope_theta=1000000.0, tie_embeddings=True, act="silu",
        norm_eps=1e-06, dtype="bfloat16", remat=False),
    "deepseek-moe-16b": dict(
        name="deepseek-moe-16b", family="moe", n_layers=9, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=10944, vocab_size=102400, head_dim=128,
        qk_norm=False, rope_theta=10000.0, tie_embeddings=True, act="silu",
        norm_eps=1e-06, dtype="bfloat16", remat=False,
        moe=dict(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                 first_k_dense=1, capacity_factor=10.7)),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_model_config_pinned(name):
    import dataclasses
    from repro.configs.base import ModelConfig, MoEConfig
    from chip import run as harness
    want = dict(PINNED[name])
    if "moe" in want:
        want["moe"] = MoEConfig(**want["moe"])
    want = ModelConfig(**want)
    got = harness.model_config(_config(name))
    for f in dataclasses.fields(ModelConfig):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
        assert type(getattr(got, f.name)) is type(getattr(want, f.name)), f.name


@pytest.mark.parametrize("edit, names", [
    (lambda c: c["serve_as"].update(d_ff=c["serve_as"]["d_ff"] + 1), r"\['d_ff'\]"),
    (lambda c: c["serve_as"]["moe"].update(top_k=5), r"\['moe.top_k'\]"),
    (lambda c: c["serve_as"].update(qk_norm=True), r"\['qk_norm'\]"),
    (lambda c: c["serve_as"].pop("moe"), r"'moe.n_experts'.*'moe.first_k_dense'"),
    (lambda c: c["serve_as"].update(n_layers=28), r"\['n_layers'\]"),
    (lambda c: c["serve_as"].update(rope_scale=2.0), "rope_scale is no field of ModelConfig"),
    (lambda c: c["serve_as"]["moe"].update(n_groups=1), "moe.n_groups is no field of MoEConfig"),
    (lambda c: c["serve_as"].update(act={"kind": "silu"}), "act holds a group"),
    (lambda c: c.pop("published"), "reduced key num_hidden_layers has no source"),
    (lambda c: c["published"].update(hidden_size=2048), "'hidden_size'.*not listed under reduced"),
    (lambda c: (c["reduced"].append("max_position_embeddings"),
                c["published"].update(max_position_embeddings=4096)),
     "max_position_embeddings is not checked"),
])
def test_model_config_refuses_what_the_file_does_not_publish(edit, names):
    from chip import run as harness
    cfg = _config("deepseek-moe-16b")
    edit(cfg)
    with pytest.raises(ValueError, match=names):
        harness.model_config(cfg)


def test_model_config_reads_groups_and_lists_generically(qwen):
    """Any field of ``ModelConfig`` may be served: a list becomes a tuple and
    a group the sub-configuration its field's type names."""
    from repro.configs.base import SSMConfig
    from chip import run as harness
    qwen["serve_as"].update(layer_pattern=["local", "global"], local_window=4,
                            ssm={"d_state": 8, "n_heads": 2, "head_dim": 4})
    mc = harness.model_config(qwen)
    assert mc.layer_pattern == ("local", "global") and mc.local_window == 4
    assert mc.ssm == SSMConfig(d_state=8, n_heads=2, head_dim=4)


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
