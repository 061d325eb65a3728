"""The device: the benchmark's own peak table, the check that a chip is
there, and the persistent compile cache inside the checkout."""
from __future__ import annotations

import os
import sys

from chipbench.harness import paths

#: Published peaks by ``device_kind``.  Source: Google Cloud documentation,
#: "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB HBM at 819 GB/s per chip.  A kind not listed is an error.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9},
}

#: fixed, so a later run in the same checkout finds what an earlier one
#: compiled (the directory is part of the cache's key)
CACHE_DIR = paths.ROOT / ".jax_cache"


def peaks(kind: str):
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[kind]


def enable_compile_cache() -> str:
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def require_chips(chips: int):
    """The devices, or exit non-zero without a result when JAX finds no
    TPU or fewer chips than the cell needs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(3)
    return devs[:chips]


def info(devs) -> dict:
    d = devs[0]
    stats = d.memory_stats() or {}
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs),
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
