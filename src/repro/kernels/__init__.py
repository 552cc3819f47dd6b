"""Pallas kernels of the paper's hot spots (conv, pool, softmax, transpose).

``resolve_interpret`` is the one place that decides whether a kernel runs in
the Pallas interpreter: explicitly when the caller says so, otherwise exactly
when the default backend is not a TPU.  Every kernel entry point on the CNN
inference path takes ``interpret=None`` and resolves it here, so on the chip
Mosaic compiles every kernel and on the CPU the same calls interpret.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` when given, else True off-TPU and False on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
