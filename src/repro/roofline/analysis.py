"""Three-term roofline model over the AOT-compiled artifact.

Per-chip peaks live in :data:`PEAKS`, keyed by ``jax.Device.device_kind``,
one row per device kind with the source of its numbers.  A device kind with
no row is an error (:func:`peaks_for`), never a silent v5e default.

Terms (per §Roofline of the assignment):
    compute    = HLO_FLOPs(per-device) / peak
    memory     = HLO_bytes(per-device) / hbm_bw
    collective = collective_link_bytes(per-device) / link_bw

``cost_analysis()`` on an SPMD executable reports per-device numbers, so no
division by chip count is needed.  MODEL_FLOPS uses 6·N·D (dense) or
6·N_active·D (MoE) with N excluding embeddings, D = tokens processed.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Hardware:
    """Per-chip peaks of one device kind and where they come from."""

    name: str
    peak_flops: float          # bf16 FLOP/s
    hbm_bw: float              # device-memory bytes/s
    link_bw: float             # bytes/s per chip-to-chip link
    hbm_bytes: float           # device memory
    source: str


#: per-chip peaks keyed by ``jax.Device.device_kind``
PEAKS: Dict[str, Hardware] = {
    # a TPU v5e chip reports its kind as "TPU v5 lite"
    "TPU v5 lite": Hardware(
        "tpu-v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9,
        hbm_bytes=16e9,
        source="Google Cloud TPU v5e documentation: 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip "
               "interconnect (4 links -> 50 GB/s each)"),
    # the XLA CPU backend has no published peak; these are deliberately
    # generous bounds for a many-core host so the admission floor they give
    # stays below any real CPU step time.  Not a measurement.
    "cpu": Hardware(
        "cpu-host", peak_flops=2e12, hbm_bw=200e9, link_bw=200e9,
        hbm_bytes=64e9,
        source="upper bounds for a many-core host, not a measured peak"),
}

#: the dry-run's target chip
V5E = PEAKS["TPU v5 lite"]


def peaks_for(device=None) -> Hardware:
    """Peaks of ``device`` (default: JAX's first device) by its
    ``device_kind``; a kind with no :data:`PEAKS` row raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no peak row for device kind {device.device_kind!r} (platform "
            f"{device.platform!r}); add one with its source to "
            f"repro.roofline.analysis.PEAKS") from None


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   coll_bytes_per_device: float, hw: Hardware) -> Dict:
    compute = flops_per_device / hw.peak_flops
    memory = bytes_per_device / hw.hbm_bw
    collective = coll_bytes_per_device / hw.link_bw
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    dom = max(terms, key=terms.get)
    bound = max(compute, memory, collective)
    terms.update({
        "dominant": dom.replace("_s", ""),
        "step_time_lb_s": bound,
        # fraction of the bound spent doing useful math = how close the cell
        # sits to its compute roofline
        "roofline_fraction": (compute / bound) if bound > 0 else 0.0,
    })
    return terms


def model_flops(n_params_active: int, tokens: int, kind: str = "train") -> float:
    """6·N·D for train (fwd+bwd), 2·N·D for inference forward."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * tokens
