"""Kernel dispatch: TPU Pallas kernel | interpret mode | pure-jnp reference.

  * mode="auto":      compiled Pallas where JAX's default backend is a TPU
                      (the chip path), the jnp reference everywhere else.
  * mode="pallas":    force compiled Pallas (needs a TPU, or a described
                      TPU topology for compile-only checks).
  * mode="interpret": run the kernel body in the Pallas interpreter — how
                      the CPU test suites check the kernels' results.
  * mode="ref":       force the pure-jnp oracle.

Set globally via env REPRO_KERNEL_MODE or per-call with the ``mode`` kwarg.
On a TPU nothing falls back: "auto" resolves to "pallas" there.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_VALID = ("auto", "pallas", "interpret", "ref")


def kernel_mode(mode: Optional[str] = None) -> str:
    mode = mode or os.environ.get("REPRO_KERNEL_MODE", "auto")
    if mode not in _VALID:
        raise ValueError(f"kernel mode {mode!r} not in {_VALID}")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return mode


def pick_block(size: int, preferred: int, minimum: int = 8) -> int:
    """Largest divisor-block <= preferred for a dimension of ``size``."""
    b = min(preferred, size)
    while size % b and b > minimum:
        b -= 1
    return max(b, 1) if size % max(b, 1) == 0 else size
