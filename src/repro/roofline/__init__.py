from repro.roofline.hlo import collective_bytes  # noqa: F401
from repro.roofline.analysis import PEAKS, peaks_for, roofline_terms  # noqa: F401
