"""Pipelined all-gather matmul (collective matmul) — compute/comm overlap.

Y = X @ W with X row-sharded (m/P, d) and W column-sharded as P stacked
blocks (d, n/P): instead of all-gathering W then multiplying (a barrier),
each rank multiplies the W block it currently holds while ppermuting it to
the next rank — P steps, transfer hidden behind the matmul. This is the
standard Megatron-style TP overlap, here as a shard_map building block
(DESIGN.md §5 distributed-optimization tricks; used as a hillclimb lever
in EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def allgather_matmul_overlapped(x_shard: jax.Array, w_block: jax.Array,
                                axis: str) -> jax.Array:
    """Inside shard_map over `axis` (size P):

    x_shard (m_local, d) — this rank's rows of X;
    w_block (d, n_block) — this rank's column block r of W.
    Returns y_local (m_local, P * n_block) = x_shard @ W (all columns).
    """
    p = lax.axis_size(axis)
    r = lax.axis_index(axis)
    n_block = w_block.shape[1]
    perm = [(j, (j + 1) % p) for j in range(p)]

    def body(i, carry):
        acc, blk = carry
        # rank r holds column block (r - i) mod p at step i
        src = (r - i) % p
        y = x_shard @ blk
        acc = lax.dynamic_update_slice(acc, y.astype(acc.dtype),
                                       (0, src * n_block))
        blk = lax.ppermute(blk, axis, perm)     # overlaps with next matmul
        return acc, blk

    acc0 = jnp.zeros((x_shard.shape[0], p * n_block), jnp.float32)
    # the zero init is device-invariant; mark it varying over the ring axis
    # so the fori_loop carry types match under shard_map
    acc0 = lax.pcast(acc0, axis, to="varying")
    acc, _ = lax.fori_loop(0, p, body, (acc0, w_block))
    return acc


def allgather_matmul_barrier(x_shard: jax.Array, w_block: jax.Array,
                             axis: str) -> jax.Array:
    """Baseline: all-gather W fully, then one matmul (the barrier the
    overlapped form removes)."""
    w_all = lax.all_gather(w_block, axis, axis=1, tiled=True)  # (d, n)
    return (x_shard @ w_all).astype(jnp.float32)
