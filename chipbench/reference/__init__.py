"""Plain float32 references, one module per model family.

Each module imports nothing of the program.  It holds the family's forward
pass in straightforward ``jax.numpy``, the laws its weights are drawn
from, and the operations and bytes its prefill and decode need.
"""
