"""internlm2-20b — dense GQA decoder.

[arXiv:2403.17297; hf]  48L d_model=6144 48H (GQA kv=8) d_ff=16384 vocab=92544.

:data:`ONE_CHIP` is the share of one TPU v5e chip (16 GB HBM) in a
pipeline-parallel deployment: the 48 layers split over 6 stages of 8 layers
each, and this chip holds one stage.  Every width is the published one
(d_model 6144, 48 query heads, 8 KV heads, head_dim 128, d_ff 16384,
vocab 92544, bf16); only the depth is cut, 48 -> 8.  That is 4.26 B
parameters (8.5 GB of bf16), which leaves room for a 16-row x 2048-token
KV cache (1.07 GB) and the step's temporaries on one chip.  The embedding
and the output head stay on this stage as well, so one chip serves tokens
end to end.  ``use_pallas`` runs prefill attention through the flash
kernel.
"""
import dataclasses

from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="internlm2-20b",
    family="dense",
    num_layers=48,
    d_model=6144,
    num_heads=48,
    num_kv_heads=8,
    d_ff=16384,
    vocab_size=92544,
    microbatches=4,
    source="[arXiv:2403.17297; hf]",
))

#: one pipeline stage (8 of 48 layers) at published widths — see above
ONE_CHIP = dataclasses.replace(
    CONFIG,
    name="internlm2-20b-1chip",
    num_layers=8,
    use_pallas=True,
    source="[arXiv:2403.17297; hf] depth 48 -> 8: one stage of a "
           "6-stage pipeline on TPU v5e",
)
