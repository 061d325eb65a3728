"""Model decode step of a recurrent configuration against its roofline:
each traced decode program's least time, priced by the reference module's
``decode_cost`` (weights read once, each live row's recurrent state read
and written), summed, over the summed device time; ``decode_roofline``'s
reading, under a name of its own for the recurrent cells."""
from chipbench.metrics.decode_roofline import read  # noqa: F401
