"""Shared kernel plumbing: interpret-mode selection."""

from __future__ import annotations

import jax


def use_interpret() -> bool:
    """pl.pallas_call(interpret=True) on the CPU, where tests validate the
    kernels; compiled on a TPU. Any other platform raises: running the
    interpreter there would hide that the kernel never ran compiled."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels here compile for TPU and interpret on CPU; the "
        f"default backend is {platform!r}")
