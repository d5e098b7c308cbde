"""chip_smoke.py's phases on the CPU at tiny sizes (interpret-mode kernels).

The chip run itself needs a TPU; these tests drive the same phase functions
with a smoke config and small shapes, and check that ``main()`` refuses to
run without a TPU.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.models.model import get_config

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load()


def test_main_refuses_without_tpu(capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_script_alone_exits_nonzero(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, str(lone)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("phase", ["scans", "sorts", "compress_linrec"])
def test_operator_phase_tiny(phase, capsys):
    jobs = smoke.Jobs(require_mosaic=False)
    if phase == "scans":
        smoke.check_scans(jobs, 1000, seed=0, s=8)
    elif phase == "sorts":
        smoke.check_sorts(jobs, 2, 300, 500, seed=0, s=8)
    else:
        smoke.check_compress_linrec(jobs, 300, seed=0, s=8)
    n_jobs = len(jobs.jobs)
    jobs.run(workers=2)
    assert capsys.readouterr().out.count("  ok  ") >= n_jobs // 2


def test_check_raises_on_failure():
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")


def test_serving_phase_smoke_config(capsys):
    smoke.run_serving(get_config("qwen3-4b", smoke=True), seed=0,
                      n_requests=4, max_batch=2, prompt_lens=(4, 8),
                      new_tokens=(2, 4), page_size=4, tick_tokens=2)
    out = capsys.readouterr().out
    assert "bitwise equal to a solo ServeEngine.generate" in out
    assert "peak_bytes_in_use" in out


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips path on 4 virtual host devices (needs its own
    process: the device count is fixed when JAX starts)."""
    code = textwrap.dedent(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        smoke.run_four_chips(seed=0, devices=jax.devices()[:4], scan_n=4096,
                             sort_n=512, rows=2, vocab=300, s=8)
        print("FOUR-OK")
        """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR-OK" in r.stdout
