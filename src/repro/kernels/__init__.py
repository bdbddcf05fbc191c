"""Pallas TPU kernels, each with a jnp oracle (``ref.py``) and a jitted
wrapper (``ops.py``) that picks the path by platform."""
from __future__ import annotations

import jax


def dispatch_mode(force: str | None = None) -> str:
    """The path an ``ops`` wrapper takes: ``force`` when given ("kernel",
    "interpret" or "ref"), else the Pallas kernel on the TPU and the jnp
    oracle elsewhere.  Interpret mode is never chosen unforced."""
    return force or ("kernel" if jax.default_backend() == "tpu" else "ref")
