"""Bring-up check on one TPU chip: serve internlm2-20b through the control plane.

    python chip_smoke.py

Serves ``internlm2-20b`` at its published widths, cut to one chip's share
(8 of 48 layers, ``repro.configs.internlm2_20b.ONE_CHIP``), with random
weights from a seed: 8 ``generate`` tasks of 128- and 512-token prompts
through ``Orchestrator.submit`` and through ``ControlPlaneGateway`` +
``ControlPlaneClient``, on the slot-granular and on the paged KV layout.
It checks the served tokens and the model path's logits
(``repro.serving.smoke``).

It prints the device and the JAX and libtpu versions first, then lines
starting with ``smoke`` (times of this one run, including compilation:
a smoke run, not a benchmark), and as its last line
``{"ok": true, "device": {...}}``.  With no TPU, or when any check fails,
it exits non-zero without that line.
"""
import importlib.metadata
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BATCH = 16
MAX_SEQ = 2048
PROMPT_LENS = (128, 512)
N_TASKS = 8
MAX_NEW_TOKENS = 32
SEED = 0


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main() -> int:
    t_start = time.perf_counter()
    from repro import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"libtpu={_version('libtpu')}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 1
    print(f"smoke compile cache: {cache_dir}", flush=True)

    from repro.configs.internlm2_20b import ONE_CHIP
    from repro.serving import smoke

    smoke.run(ONE_CHIP, batch_size=BATCH, max_seq=MAX_SEQ,
              prompt_lens=PROMPT_LENS, n_tasks=N_TASKS,
              max_new_tokens=MAX_NEW_TOKENS, seed=SEED,
              log=lambda line: print(line, flush=True))
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"smoke peak device memory: {peak} bytes; whole run "
          f"{time.perf_counter() - t_start:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
