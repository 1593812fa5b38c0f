"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True off-TPU, where every kernel runs in Pallas interpret mode
    (CPU tests execute the very kernel bodies the chip compiles).  A
    TPU backend always gets the compiled kernel."""
    return jax.default_backend() != "tpu"
