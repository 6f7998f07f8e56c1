"""Pallas TPU kernel: block-sparse selected attention (DSA/NSA regime).

TPU adaptation of the token-level indexer gather (DESIGN.md §6): selection
is at 64-token *block* granularity so the gather is a BlockSpec index_map
driven by scalar-prefetched block ids — the sparse access becomes a dense
(BLOCK, D) VMEM stream per grid step, which is what the MXU wants. The
holder cost tracks the selection budget KB, not the store size (§6.3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(idx_ref, q_ref, ckv_ref, o_ref, m_ref, l_ref,
            acc, m_scr, l_scr, *, scale: float, d_v: int):
    k_idx = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k_idx == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0].astype(jnp.float32)                  # (H, D)
    blk = ckv_ref[0].astype(jnp.float32)              # (BLOCK, D) gathered
    scores = jax.lax.dot_general(
        q, blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (H, BLOCK)

    # m/l stay (H, 1) columns, as in kernels/mla_decode
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, blk[:, :d_v], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...] = m_new, l_new

    @pl.when(k_idx == nk - 1)
    def _finish():
        l = l_scr[...]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = acc[...] / denom
        m_ref[0] = m_scr[...]
        l_ref[0] = l


def sparse_select_pallas(q: jax.Array, ckv: jax.Array, block_idx: jax.Array,
                         d_v: int, scale: float, block_tokens: int = 64,
                         interpret: bool = True):
    """q (B, H, D); ckv (B, S, D); block_idx (B, KB) int32 block ids.
    S % block_tokens == 0. The index_map gathers selected blocks directly
    from HBM via scalar prefetch."""
    B, H, D = q.shape
    KB = block_idx.shape[1]
    kernel = functools.partial(_kernel, scale=scale, d_v=d_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KB),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, k, idx: (b, 0, 0)),
            # the gather: block k of batch b reads cache block idx[b, k]
            pl.BlockSpec((1, block_tokens, D),
                         lambda b, k, idx: (b, idx[b, k], 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, H, d_v), lambda b, k, idx: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, k, idx: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, k, idx: (b, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((H, d_v), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    # m/l leave as (B, H, 1): a (1, H) block of a (B, H) array breaks the
    # TPU tiling rule once B > 1
    out_shape = (jax.ShapeDtypeStruct((B, H, d_v), jnp.float32),
                 jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                 jax.ShapeDtypeStruct((B, H, 1), jnp.float32))
    o, m, l = pl.pallas_call(kernel, grid_spec=grid_spec,
                             out_shape=out_shape,
                             interpret=interpret)(block_idx, q, ckv)
    return o, m[..., 0], l[..., 0]
