"""A configuration of an architecture that ``reference/decoder.py`` does not
cover joins a copy of the benchmark as new files only, and a whole run of its
cell goes through the harness and its check on the CPU.

The architecture is a sliding-window GQA decoder (``plugin/``): its reference
module, a configuration naming it, a traffic file whose prompts are all longer
than the window, and a limits file, plus one ``configs`` and one
``workloads`` entry in ``BENCHMARK.json``.  A sound run passes and a token
altered in the engine fails; on the same run the ``decoder`` reference, and
the module's own reference with the window taken away, read a wider
``logit_gap``: the named module is what decides.

The limit, 0.05, lies between the readings of eight seeds on the CPU, all 61
finished requests read: the program 0 to 0.00017, the reference without its
window 0.39 to 0.54, the ``decoder`` reference 0.51 to 0.85, the altered
tokens 1.27 to 1.42.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
PLUGIN = Path(__file__).resolve().parent / "plugin"
CELL = "tiny-window.tiny-greedy"
NEW = {"reference/windowed.py": "windowed.py",
       "configs/tiny-window.json": "tiny-window.json",
       "traffic/tiny-greedy.json": "tiny-greedy.json",
       f"limits/{CELL}.json": f"{CELL}.json"}

# one process with the copy's harness on its path: a sound run, then one
# whose engine adds 1 to every token it produces
SCRIPT = r"""
import json, sys
sys.path[:0] = ["benchmarks", sys.argv[1]]
import jax
from chip import correct, serve, traffic
from chip import run as harness


class Clock:
    # moves 10 ms at every reading: the window holds the same ticks however
    # busy the CPU
    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 0.01
        return self.t


def run(**kw):
    serve.time = Clock()
    cell, cfg, e2e, per_layer = harness.load_cell(CELL)
    return harness.run_cell(
        cell, cfg, traffic.load(cell["traffic"]), e2e, per_layer,
        seed=2**31 + 17, seconds=2.0, traced=False, devices=jax.devices(),
        limits=correct.limits(CELL),
        peak_row={"bf16_flops": 1e12, "hbm_bw": 1e11}, **kw)


# at this width a reference that differs moves a greedy choice only now and
# then: read every finished request, not a sample of four
correct.SAMPLE = 1000
readings, other = correct.readings, {}


def with_others(cfg, *args, **kw):
    other["decoder"] = readings(dict(cfg, reference="decoder"), *args, **kw)
    other["no_window"] = readings(dict(cfg, sliding_window=10**6), *args, **kw)
    return readings(cfg, *args, **kw)


correct.readings = with_others
sound = run()
correct.readings = readings


def alter(engine):
    orig = engine._sample_rows
    engine._sample_rows = lambda logits, keys: (orig(logits, keys) + 1) % 500


altered = run(engine_hook=alter)
print(json.dumps({"sound": sound, "altered": altered, **other}))
""".replace("CELL", repr(CELL))


def _digests(top):
    return {p.relative_to(top).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(top.rglob("*")) if p.is_file()}


def _copy_with_plugin(dst):
    """The benchmark as committed, then the plug-in's new files and entries."""
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, dst / d, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(dst)
    chip = dst / "benchmarks" / "chip"
    for rel, src in NEW.items():
        assert not (chip / rel).exists(), rel
        shutil.copy(PLUGIN / src, chip / rel)
    bench["configs"].append({
        "name": "tiny-window", "source": "none: a test configuration",
        "file": "benchmarks/chip/configs/tiny-window.json", "reduced": [],
        "why": "sliding-window and full attention layers in turn"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-window", "traffic": "tiny-greedy",
        "chips": 1, "why": "prompts 8-24 against a window of 6"})
    with open(dst / "BENCHMARK.json", "w") as f:
        json.dump(bench, f, indent=1)
    return before


def test_new_architecture_joins_as_new_files(tmp_path):
    before = _copy_with_plugin(tmp_path)
    after = _digests(tmp_path)
    changed = {k for k in before if after[k] != before[k]}
    assert changed == {"BENCHMARK.json"}
    with open(ROOT / "BENCHMARK.json") as f:
        old = json.load(f)
    with open(tmp_path / "BENCHMARK.json") as f:
        new = json.load(f)
    for k, v in old.items():
        # the lists only gain entries at their ends
        assert new[k][:len(v)] == v if isinstance(v, list) else new[k] == v
    assert set(after) - set(before) == {f"benchmarks/chip/{r}" for r in NEW}

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT / "src")],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.splitlines()[-1])
    sound, altered = out["sound"], out["altered"]
    limit = sound["checks"]["logit_gap"]["limit"]
    assert sound["correct"] is True, sound["checks"]
    assert sound["attempted"] > 20 and sound["failed"] == 0
    assert altered["correct"] is False
    assert altered["checks"]["logit_gap"]["value"] > limit
    gap = sound["checks"]["logit_gap"]["value"]
    assert out["decoder"]["logit_gap"] > max(gap, limit)
    assert out["no_window"]["logit_gap"] > max(gap, limit)
