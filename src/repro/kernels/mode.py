"""The one place that picks how a Pallas kernel runs.

Compiled (Mosaic) on a TPU backend, interpreted everywhere else. Every
kernel entry point resolves its ``interpret`` argument here at trace time;
heads and launchers never pass it. An explicit ``True``/``False`` is an
override for tests — e.g. compiling for a described TPU from a CPU process,
where the backend says "cpu" but the target is the chip.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else ``True`` unless the backend is a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
