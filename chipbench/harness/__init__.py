"""Shared code of the chip benchmark: set-up, load, statistics, trace
reduction, the peak table and the output check."""
