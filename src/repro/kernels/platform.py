"""Which way a Pallas kernel runs on the current backend."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` follows the platform: compiled Mosaic on a TPU, the
    Pallas interpreter anywhere else. An explicit bool always wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
