"""Pallas TPU kernel: causal flash attention over the latent cache
(absorbed-MLA prefill — fills the canonical c^KV store while computing).

Tiling: grid (B, Sq/BQ, Sk/BK), k innermost (sequential accumulation).
Causal block skipping: a (BQ, BK) tile is skipped when its query block ends
before its key block starts — upper-triangle tiles cost nothing, the
classic flash schedule. Heads fold into the q tile (H*BQ rows) so the MXU
sees a tall-skinny (H*BQ, D) @ (D, BK) matmul with D = 576.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(q_ref, ckv_ref, o_ref, acc, m_scr, l_scr,
            *, scale: float, d_v: int, block_q: int, block_k: int,
            sq: int, sk: int):
    k_idx = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q_idx = pl.program_id(1)
    q_end = (q_idx + 1) * block_q - 1 + (sk - sq)     # last query's kv reach
    k_start = k_idx * block_k

    @pl.when(k_start <= q_end)                        # causal block skip
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (BQ, H, D)
        BQ, H, D = q.shape
        qf = q.reshape(BQ * H, D)
        kv = ckv_ref[0].astype(jnp.float32)           # (BK, D)
        scores = jax.lax.dot_general(
            qf, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ*H, BK)
        # row r of the folded tile is query position q0 + r // H. Causality
        # kpos <= q0 + r // H is tested as r >= (kpos - q0) * H, which is
        # the same for integers: 2-D iotas and a multiply, no (BQ, H) ->
        # (BQ*H, 1) reshape, which Mosaic cannot lower
        q0 = q_idx * block_q + (sk - sq)
        row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(row >= (kpos - q0) * H, scores, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new[:, None])
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p, kv[:, :d_v], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(k_idx == nk - 1)
    def _finish():
        l = l_scr[...]
        denom = jnp.where(l > 0, l, 1.0)
        BQ = o_ref.shape[1]
        H = o_ref.shape[2]
        o_ref[0] = (acc[...] / denom[:, None]).reshape(BQ, H, d_v)


# Folded q rows (block_q * H) per tile when block_q is not given: the f32
# (rows, block_k) score tile, its exp and the (rows, d_v) accumulator must
# fit the 16 MiB scoped VMEM. At H=128, D=576, block_k=512 a v5e compile
# fits 8 queries (1024 rows) and refuses 16.
FOLDED_ROWS = 1024


def flash_prefill_pallas(q: jax.Array, ckv: jax.Array, d_v: int,
                         scale: float, block_q: Optional[int] = None,
                         block_k: int = 512, interpret: bool = True):
    """q (B, Sq, H, D); ckv (B, Sk, D) with Sq <= Sk, tail-aligned causal."""
    B, Sq, H, D = q.shape
    Sk = ckv.shape[1]
    if block_q is None:
        block_q = max(1, FOLDED_ROWS // H)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    kernel = functools.partial(_kernel, scale=scale, d_v=d_v,
                               block_q=block_q, block_k=block_k,
                               sq=Sq, sk=Sk)
    return pl.pallas_call(
        kernel,
        grid=(B, Sq // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, H, D), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, H, d_v),
                               lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H, d_v), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_q * H, d_v), jnp.float32),
            pltpu.VMEM((block_q * H,), jnp.float32),
            pltpu.VMEM((block_q * H,), jnp.float32),
        ],
        interpret=interpret,
    )(q, ckv)
