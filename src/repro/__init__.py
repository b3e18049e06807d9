"""repro: PANN (power-aware neural networks) as a production JAX framework."""
import os

__version__ = "0.1.0"

# The checkout this package runs from (src/repro/ -> the repo root). Run-time
# caches live at fixed, gitignored paths under it, so every run of one
# checkout finds what an earlier run left there.
CHECKOUT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
