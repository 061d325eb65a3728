"""The chip bring-up path, at ``reduced()`` size on the CPU.

``chip_smoke.py`` runs :func:`repro.serving.smoke.run` on one TPU chip at
published widths.  Here the same function serves a reduced internlm2
through the orchestrator and the gateway on both KV layouts, with the
flash kernel in prefill (interpreted), and runs the same checks.  The
remaining tests pin the contracts around it: no result off the chip, no
v5e peaks for another device, and where the compile cache goes.
"""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.configs import get_config, reduced
from repro.roofline.analysis import PEAKS, peaks_for

ROOT = Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.slow    # heavy suite: excluded from make test-fast


def test_serving_smoke_passes_at_reduced_size():
    from repro.serving import smoke

    cfg = reduced(get_config("internlm2-20b"), use_pallas=True)
    lines = []
    report = smoke.run(cfg, batch_size=4, max_seq=64, prompt_lens=(12, 24),
                       n_tasks=4, max_new_tokens=6, seed=3, log=lines.append)
    tokens = report["tokens"]
    assert tokens["slot"] == tokens["paged"]
    assert len(tokens["slot"]) == 2 + 4               # warm-up + served
    assert all(len(t) == 6 for t in tokens["slot"])
    num = report["numerics"]
    assert num["rtol"] == smoke.LOGIT_RTOL["float32"]
    assert num["flash_vs_jnp"] <= num["rtol"]
    assert num["decode_vs_prefill"] <= num["rtol"]
    assert any(line.startswith("smoke numerics") for line in lines)


def _run(code_or_script, env_extra, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT / "src")},
               **env_extra)
    args = ([sys.executable, str(code_or_script)]
            if isinstance(code_or_script, Path)
            else [sys.executable, "-c", code_or_script])
    return subprocess.run(args, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def test_chip_smoke_refuses_to_run_off_the_chip():
    proc = _run(ROOT / "chip_smoke.py", {})
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(tmp_path, env_dir):
    code = ("import jax; from repro import compile_cache; "
            "print(compile_cache.enable()); "
            "print(jax.config.jax_compilation_cache_dir)")
    extra = {}
    if env_dir is not None:
        extra["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    proc = _run(code, extra)
    assert proc.returncode == 0, proc.stderr
    used, configured = proc.stdout.split()
    want = (str(tmp_path / env_dir) if env_dir is not None
            else str(ROOT / ".jax_cache"))
    assert used == configured == want


def test_peaks_keyed_by_device_kind():
    assert peaks_for().name == "cpu-host"            # this backend's row
    assert peaks_for(SimpleNamespace(device_kind="TPU v5 lite",
                                     platform="tpu")).peak_flops == 197e12
    with pytest.raises(KeyError, match="no peak row"):
        peaks_for(SimpleNamespace(device_kind="TPU v99", platform="tpu"))
    assert all(hw.source for hw in PEAKS.values())
