"""Cross-instance query routing — "move the query, not the cache" (§2, §3.3).

The paper's ROUTE primitive, adapted to TPU (DESIGN.md §2): instances are
shards along a mesh axis; the device-initiated put becomes a compiler-issued
collective inside shard_map. Three transport schedules are provided:

* fanout  : all_gather(q) -> per-holder partial -> all_to_all(partials) ->
            local M-way merge. The scattered-selection regime (§5.4); one
            barrier-free round, matches the paper's "ship the query once,
            merge M partials".
* pairwise: ppermute to a single holder and back — the §4 microbenchmark
            shape (one requester, one holder), minimal wire bytes.
* ring    : the query + merge accumulator circulate the ring; each hop
            overlaps the next hop's transfer with the current partial's
            compute (beyond-paper optimization; decode-form ring attention).

All three reproduce single-instance attention exactly (to float round-off):
the online-softmax merge is associative + commutative with an identity
(core/merge.py), so the result is invariant to how the cache is partitioned
— the paper's §3.3 exactness claim, which tests/test_routing.py verifies.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.merge import Partial, merge2, merge_stacked, merge_tree
from repro.models.mla import MLAConfig, absorbed_partial


# ---------------------------------------------------------------------------
# Single-process simulation (oracle semantics; used by unit tests and the
# serving engine's single-host mode).
# ---------------------------------------------------------------------------

def route_simulated(cfg: MLAConfig, q_abs: jax.Array,
                    shards: Sequence[jax.Array],
                    masks: Optional[Sequence[jax.Array]] = None) -> Partial:
    """Merge partial attention over an arbitrary partition of the cache.

    q_abs (..., H, d_qk); shards: list of (S_i, d_qk) resident subsets.
    Equivalent to attention over concat(shards) regardless of partitioning.
    """
    parts = []
    for i, shard in enumerate(shards):
        mask = None if masks is None else masks[i]
        parts.append(absorbed_partial(cfg, q_abs, shard, mask))
    return merge_tree(parts)


def route_batched(cfg: MLAConfig, queries: Sequence[jax.Array],
                  holder_shards: Sequence[Sequence[jax.Array]],
                  masks: Optional[Sequence[Sequence[jax.Array]]] = None
                  ) -> "list[Partial]":
    """Batched multi-holder routing, keyed by a dispatch plan: group g ships
    queries[g] (the plan's stacked requester rows, (m_q_total, H, d_qk)) to
    every holder in holder_shards[g] and returns the g-th merged Partial.

    This is the serving engine's exec-mode entry (ISSUE 3): one planned
    dispatch = one group = one holder-side batched partial per holder (the
    §6.3 "batched partial is ~free" kernel shape), merged requester-side.
    Semantically each group is route_simulated — so outputs are exact to
    round-off under any partitioning — but the per-group batching mirrors
    the per-(holder, chunk, fabric) dispatch batching the planner already
    did, instead of re-deriving per-request calls.
    """
    if len(queries) != len(holder_shards):
        raise ValueError(
            f"{len(queries)} query groups vs {len(holder_shards)} shard sets")
    return [route_simulated(cfg, q, shards,
                            None if masks is None else masks[g])
            for g, (q, shards) in enumerate(zip(queries, holder_shards))]


# ---------------------------------------------------------------------------
# shard_map collectives (production path; `axis` is the instance mesh axis).
# These run inside shard_map — callers supply per-shard arrays. The bodies
# are split at collective boundaries into named stage functions so the
# shard_map exec backend (ISSUE 7) can time each wire/compute stage
# individually; route_fanout / route_pairwise stay the fused compositions.
# ---------------------------------------------------------------------------

def check_route_shards(axis: str, q_abs: jax.Array, local_ckv: jax.Array,
                       local_valid: Optional[jax.Array] = None,
                       shard: Optional[int] = None) -> None:
    """Up-front shard-shape validation (ISSUE 7 satellite). A per-shard
    B / S_local disagreement used to surface only as an opaque XLA
    all_to_all / scan shape error deep in lowering; shapes are trace-time
    constants, so every expressible mismatch can be rejected here with the
    axis, the offending shard (when the caller knows it — per-shard input
    assembly does) and both shapes in the message."""
    where = f"mesh axis {axis!r}" + ("" if shard is None
                                     else f", shard {shard}")
    if q_abs.ndim < 2:
        raise ValueError(
            f"route shard on {where}: q_abs must be (..., B, H, d_qk), got "
            f"shape {tuple(q_abs.shape)}")
    if local_ckv.ndim != 2:
        raise ValueError(
            f"route shard on {where}: local_ckv must be (S_local, d_qk), "
            f"got shape {tuple(local_ckv.shape)}")
    if q_abs.shape[-1] != local_ckv.shape[-1]:
        raise ValueError(
            f"route shards disagree on {where}: q_abs has d_qk="
            f"{q_abs.shape[-1]} but local_ckv has d_qk={local_ckv.shape[-1]} "
            f"(shapes {tuple(q_abs.shape)} vs {tuple(local_ckv.shape)})")
    if local_valid is not None \
            and tuple(local_valid.shape) != (local_ckv.shape[0],):
        raise ValueError(
            f"route shards disagree on {where}: local_valid covers "
            f"S_local={local_valid.shape[0] if local_valid.ndim else '?'} "
            f"entries but local_ckv holds S_local={local_ckv.shape[0]} "
            f"(shapes {tuple(local_valid.shape)} vs "
            f"{tuple(local_ckv.shape)})")


def fanout_gather(q_abs: jax.Array, axis: str = "instance") -> jax.Array:
    """Fanout wire stage 1 (transfer): broadcast every instance's query
    rows — (B, H, d) per shard -> (M, B, H, d) everywhere."""
    return lax.all_gather(q_abs, axis)


def fanout_exchange(part: Partial, axis: str = "instance",
                    wire_dtype=None) -> Partial:
    """Fanout wire stage 2 (return): deliver partials back — slice m of
    the leading axis -> instance m. wire_dtype=bf16 gives the paper's
    1032-B partial row (o bf16, m/l f32 — §3.2); None keeps full precision
    (exactness tests)."""
    o_wire = part.o if wire_dtype is None else part.o.astype(wire_dtype)
    # barrier: keep the downstream f32 upcast from hoisting across the
    # collective (would double the partial's wire bytes — §Perf P1)
    o = lax.optimization_barrier(
        lax.all_to_all(o_wire, axis, split_axis=0, concat_axis=0))
    m = lax.all_to_all(part.m, axis, split_axis=0, concat_axis=0)
    l = lax.all_to_all(part.l, axis, split_axis=0, concat_axis=0)
    return Partial(o=o.astype(jnp.float32), m=m, l=l)


def route_fanout(cfg: MLAConfig, q_abs: jax.Array, local_ckv: jax.Array,
                 local_valid: jax.Array, axis: str = "instance",
                 partial_fn: Optional[Callable] = None,
                 wire_dtype=None) -> Partial:
    """Scattered multi-holder route (§5.4). Every instance is requester and
    holder at once (the agentic fan-in of §1).

    Per-shard shapes: q_abs (B, H, d_qk) — this instance's decode queries;
    local_ckv (S_local, d_qk) — resident canonical entries; local_valid
    (S_local,) bool — residency mask (scattered selection sets it per step).
    Returns this instance's fully-merged Partial (B, H, .).
    """
    check_route_shards(axis, q_abs, local_ckv, local_valid)
    qs = fanout_gather(q_abs, axis)                     # (M, B, H, d)
    fn = partial_fn or (lambda q, c, v: absorbed_partial(cfg, q, c, v))
    part = fn(qs, local_ckv, local_valid)               # (M, B, H, ...) on holder
    ex = fanout_exchange(part, axis, wire_dtype)
    return merge_stacked(ex.o, ex.m, ex.l)              # (B, H, ...)


def pairwise_ship(q_abs: jax.Array, holder: int, requester: int,
                  axis: str = "instance") -> jax.Array:
    """Pairwise wire stage 1 (transfer): the requester's query rows move to
    the holder — one ppermute = the §4 put."""
    # optimization_barrier pins the wire dtype against convert-hoisting
    # across the collective. NOTE (EXPERIMENTS.md §Perf P1): on the CPU
    # backend the permute STILL lowers as f32 — XLA:CPU float-normalizes
    # bf16 collectives (verified on a bare bf16 ppermute); on TPU bf16
    # collectives are native, so the 1152-B wire row holds there.
    return lax.optimization_barrier(
        lax.ppermute(q_abs, axis, [(requester, holder)]))


def pairwise_return(part: Partial, holder: int, requester: int,
                    axis: str = "instance", wire_dtype=None) -> Partial:
    """Pairwise wire stage 2 (return): the holder's partial travels back."""
    o_wire = part.o if wire_dtype is None else part.o.astype(wire_dtype)
    return Partial(
        o=lax.optimization_barrier(
            lax.ppermute(o_wire, axis,
                         [(holder, requester)])).astype(jnp.float32),
        m=lax.ppermute(part.m, axis, [(holder, requester)]),
        l=lax.ppermute(part.l, axis, [(holder, requester)]),
    )


def route_pairwise(cfg: MLAConfig, q_abs: jax.Array, local_ckv: jax.Array,
                   local_partial: Partial, holder: int, requester: int,
                   axis: str = "instance", wire_dtype=None,
                   local_valid: Optional[jax.Array] = None) -> Partial:
    """Single-holder route (§4 microbenchmark shape): requester ships q to
    holder (one ppermute = the put), holder computes the partial over its
    resident chunk (through local_valid when the selection regime chose a
    subset — §5.4), partial returns, requester merges with its own local
    partial (its private suffix)."""
    check_route_shards(axis, q_abs, local_ckv, local_valid)
    q_at_holder = pairwise_ship(q_abs, holder, requester, axis)
    part = absorbed_partial(cfg, q_at_holder, local_ckv, local_valid)
    back = pairwise_return(part, holder, requester, axis, wire_dtype)
    return merge2(local_partial, back)


def route_ring(cfg: MLAConfig, q_abs: jax.Array, local_ckv: jax.Array,
               local_valid: jax.Array, axis: str = "instance") -> Partial:
    """Ring-scheduled route: each hop ppermutes (q, acc) one step while the
    holder computes the visiting query's partial. After M hops the query is
    home with the full merge. Overlaps transfer with compute (beyond-paper;
    the TPU-native schedule for all-holders attention)."""
    check_route_shards(axis, q_abs, local_ckv, local_valid)
    m_size = lax.axis_size(axis)
    perm = [(i, (i + 1) % m_size) for i in range(m_size)]

    def hop(carry, _):
        q, acc = carry
        part = absorbed_partial(cfg, q, local_ckv, local_valid)
        acc = merge2(acc, part)
        q = lax.ppermute(q, axis, perm)
        acc = Partial(o=lax.ppermute(acc.o, axis, perm),
                      m=lax.ppermute(acc.m, axis, perm),
                      l=lax.ppermute(acc.l, axis, perm))
        return (q, acc), None

    ident = Partial.identity(q_abs.shape[:-1], cfg.kv_lora_rank)
    # the identity carry is device-invariant; mark it varying over the
    # instance axis so the scan carry types line up under shard_map
    ident = jax.tree.map(lambda x: lax.pcast(x, axis, to="varying"), ident)
    (q, acc), _ = lax.scan(hop, (q_abs, ident), None, length=m_size)
    return acc


# ---------------------------------------------------------------------------
# TPLA rank-paired routing (§8 "Tensor parallelism"): the latent is
# column-partitioned across TP ranks; A.rank_r ships only its d_qk/N query
# slice to B.rank_r, the cross-rank reduction stays inside each instance.
# Per-rank inter-instance bytes fall 1/N.
# ---------------------------------------------------------------------------

def route_pairwise_tpla(cfg: MLAConfig, q_abs_slice: jax.Array,
                        local_ckv_slice: jax.Array, holder: int,
                        requester: int, instance_axis: str = "instance",
                        tp_axis: str = "tp") -> Partial:
    """Per-shard shapes: q_abs_slice (B, H, d_qk/N) — this rank's latent
    columns; local_ckv_slice (S, d_qk/N) — same columns of the holder's cache.

    Logits decompose as a sum over latent columns => per-rank partial logits
    psum over the *intra-instance* tp axis (NVLink-analogue: ICI), then each
    rank computes its own d_v/N output slice. Only the (1/N-sized) query and
    output slices cross the instance axis.
    """
    q_h = lax.ppermute(q_abs_slice, instance_axis, [(requester, holder)])
    # Partial logit contribution from this rank's columns.
    logits_r = jnp.einsum("bhc,sc->bhs", q_h.astype(jnp.float32),
                          local_ckv_slice.astype(jnp.float32)) * cfg.scale
    logits = lax.psum(logits_r, tp_axis)               # intra-instance
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    # Each rank holds d_c/N value columns; output slice stays rank-local.
    n_tp = lax.axis_size(tp_axis)
    v_cols = local_ckv_slice[:, :cfg.kv_lora_rank // n_tp].astype(jnp.float32)
    o_slice = jnp.einsum("bhs,sd->bhd", p / l[..., None], v_cols)
    back = Partial(
        o=lax.ppermute(o_slice, instance_axis, [(holder, requester)]),
        m=lax.ppermute(m, instance_axis, [(holder, requester)]),
        l=lax.ppermute(l, instance_axis, [(holder, requester)]),
    )
    return back
