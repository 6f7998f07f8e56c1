"""Pallas TPU kernel: absorbed-MLA flash decode (FlashMLA analogue).

The holder-side hot-spot of ROUTE (§6.3): a small batch of absorbed query
rows (B requesters x H heads, each d_qk=576 wide) attends the resident
latent cache. TPU-native tiling (DESIGN.md §6):

* grid (B, S/BS): batch major, cache blocks minor (sequential) — the online
  -softmax accumulator lives in VMEM scratch across the S sweep;
* q tile (H, D) stays resident; one (BS, D) c^KV tile streams HBM->VMEM per
  step; BS=512 rows x 576 lanes x 2 B ~ 0.6 MB — well inside VMEM, and the
  (H x D) @ (D x BS) score matmul feeds the MXU with a 128-multiple
  contraction (576 = 4.5 x 128; H pads to the sublane quantum);
* the value contraction reuses the SAME resident tile (values are the first
  d_v=512 lanes of the latent entry — MLA's byte-asymmetry trick), so no
  second stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(len_ref, q_ref, ckv_ref, o_ref, m_ref, l_ref,
            acc, m_scr, l_scr, *, scale: float, d_v: int, block_s: int):
    b_idx = pl.program_id(0)
    s_idx = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0].astype(jnp.float32)                  # (H, D)
    ckv = ckv_ref[0].astype(jnp.float32)              # (BS, D)
    scores = jax.lax.dot_general(
        q, ckv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (H, BS)
    # residency mask for the ragged tail (valid cache length per batch row)
    valid = (s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)) < len_ref[b_idx]
    scores = jnp.where(valid, scores, NEG_INF)

    # m/l stay (H, 1) columns end to end: the row reductions keep their
    # sublane layout, so no relayout is needed to store them
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                   # exp(-inf - m) = 0 ok
    p = jnp.exp(scores - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, ckv[:, :d_v], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...] = m_new, l_new

    @pl.when(s_idx == ns - 1)
    def _finish():
        l = l_scr[...]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = acc[...] / denom
        m_ref[0] = m_scr[...]
        l_ref[0] = l


def mla_decode_pallas(q: jax.Array, ckv: jax.Array, lengths: jax.Array,
                      d_v: int, scale: float, block_s: int = 512,
                      interpret: bool = True):
    """q (B, H, D); ckv (B, S, D); lengths (B,) valid entries per row."""
    B, H, D = q.shape
    S = ckv.shape[1]
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    kernel = functools.partial(_kernel, scale=scale, d_v=d_v,
                               block_s=block_s)
    # lengths ride in SMEM as a scalar-prefetch operand. m/l leave the
    # kernel as (B, H, 1) so every block's last two dims are (H, 1): whole
    # array dims or tile multiples, as Mosaic requires for any batch size.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // block_s),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, s, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_s, D), lambda b, s, lens: (b, s, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, H, d_v), lambda b, s, lens: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, s, lens: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, s, lens: (b, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((H, d_v), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    out_shape = (jax.ShapeDtypeStruct((B, H, d_v), jnp.float32),
                 jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                 jax.ShapeDtypeStruct((B, H, 1), jnp.float32))
    o, m, l = pl.pallas_call(kernel, grid_spec=grid_spec,
                             out_shape=out_shape,
                             interpret=interpret)(lengths, q, ckv)
    return o, m[..., 0], l[..., 0]
