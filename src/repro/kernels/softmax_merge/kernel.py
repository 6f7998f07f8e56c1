"""Pallas TPU kernel: M-way (o, m, l) online-softmax merge.

The requester-side recombination of ROUTE (<=25 us in the paper, §4.2).
One fused pass: m* = max_i m_i, w_i = l_i exp(m_i - m*), o* = sum w_i o_i /
sum w_i. Grid over B; the (M, H, d_v) partial stack for one requester batch
row fits VMEM for any realistic fan-in (M <= 16, §6.3 elbow).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(o_ref, m_ref, l_ref, oo_ref, mo_ref, lo_ref):
    o = o_ref[:, 0].astype(jnp.float32)               # (M, H, d_v)
    m = m_ref[:, 0].astype(jnp.float32)               # (M, H, 1)
    l = l_ref[:, 0].astype(jnp.float32)
    m_star = jnp.max(m, axis=0)                       # (H, 1)
    safe = jnp.where(jnp.isfinite(m_star), m_star, 0.0)
    w = l * jnp.exp(m - safe[None])                   # exp(-inf)=0: identity
    l_star = jnp.sum(w, axis=0)
    denom = jnp.where(l_star > 0, l_star, 1.0)
    # the M-way weighted sum runs over the leading axis: elementwise
    # multiply-adds on (H, d_v) tiles, no batched matmul
    oo_ref[0] = jnp.sum((w / denom[None]) * o, axis=0)
    mo_ref[0] = jnp.where(l_star > 0, m_star, NEG_INF)
    lo_ref[0] = l_star


def softmax_merge_pallas(o: jax.Array, m: jax.Array, l: jax.Array,
                         interpret: bool = True):
    """o (M, B, H, d_v); m/l (M, B, H).

    m/l travel as (M, B, H, 1) so each block's last two dims are (H, 1):
    a (1, H) slice of a (B, H) array breaks the TPU tiling rule once
    B > 1."""
    M, B, H, d_v = o.shape
    oo, mo, lo = pl.pallas_call(
        _kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((M, 1, H, d_v), lambda b: (0, b, 0, 0)),
            pl.BlockSpec((M, 1, H, 1), lambda b: (0, b, 0, 0)),
            pl.BlockSpec((M, 1, H, 1), lambda b: (0, b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, H, d_v), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b: (b, 0, 0)),
        ),
        out_shape=(jax.ShapeDtypeStruct((B, H, d_v), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32)),
        interpret=interpret,
    )(o, m[..., None], l[..., None])
    return oo, mo[..., 0], lo[..., 0]
