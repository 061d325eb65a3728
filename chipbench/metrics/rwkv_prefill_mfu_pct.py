"""Model prefill step of a recurrent configuration: the admission
prefills' operations, counted from shapes by the reference module (every
projection and the recurrence on every token, the head on the last), over
the traced admission programs' device time times the chip's peak FLOP/s;
``prefill_mfu_pct``'s reading, under a name of its own for the recurrent
cells."""
from chipbench.metrics.prefill_mfu_pct import read  # noqa: F401
