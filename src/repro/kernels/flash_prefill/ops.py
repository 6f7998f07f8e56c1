"""jit'd public wrapper for causal latent flash prefill."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.common import use_interpret
from repro.kernels.flash_prefill.kernel import flash_prefill_pallas


@functools.partial(jax.jit, static_argnames=("d_v", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_prefill(q: jax.Array, ckv: jax.Array, *, d_v: int = 512,
                  scale: float = 1.0, block_q: Optional[int] = None,
                  block_k: int = 512,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Causal absorbed-MLA attention: q (B,Sq,H,D) over ckv (B,Sk,D)."""
    interp = use_interpret() if interpret is None else interpret
    return flash_prefill_pallas(q, ckv, d_v, scale, block_q, block_k, interp)
