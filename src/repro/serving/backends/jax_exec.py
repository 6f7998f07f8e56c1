"""JaxExecBackend: run dispatch plans on real jax arrays.

The planner decides ROUTE / FETCH / LOCAL per (holder, chunk, fabric)
group; this backend EXECUTES those decisions:

* chunks materialize as real c^KV arrays (S, d_qk) in the chunk store —
  deterministic per chunk_id, so a re-run (or the exactness oracle) sees
  the same cache bytes;
* ROUTE — the grouped requesters' query tensors are stacked into one
  holder-side batched partial (core.routing.route_batched: the §6.3
  "batched partial is ~free" holder kernel), sliced back per request,
  merged requester-side. The query moved, the cache did not.
* FETCH — the chunk replicates through the core.splice path (delta-0
  re-home: the rotation is the identity, §6.3 true-prefix case), the copy
  is stored as the replica's array, and the requesters attend it LOCALLY —
  the cache moved, exactly as priced.
* LOCAL — re-prefill: the canonical entries are recomputed at the
  requester (same deterministic materialization) and attended locally.
* resident pairs (no transport planned) attend their local copy.

Under an ACTIVE selection (ISSUE 4 — the plan carries the indexer's masks
in StepPlan.selections), every primitive narrows to the chosen set:
ROUTE executes as a MASKED partial on the holder (selected & resident in
place — "the indexer's choice made distributed", §5.4; semantically the
block-sparse attend kernels/sparse_select computes), FETCH becomes the
scattered gather core.splice models (pull ONLY the selected entries at
canonical positions — no splice, nothing persisted), LOCAL and resident
accesses attend through the mask. The merged outputs then reproduce
single-instance selection_k decode (the DSA path of models/model.py) to
float round-off — selection_oracle_partial is that reference.

Every request's per-chunk partials merge through the online-softmax merge
(core.merge) — associative + commutative with identity — so the final
output per request equals single-instance attention over the concatenated
chunks to float round-off REGARDLESS of which primitive the predicate
picked (§3.3, now end-to-end through the scheduler).

Every holder partial runs as one compiled program (`HolderPrograms`):
`holder_partial` for a resident, local or fetched access and for a
one-member route, keyed by the query's m_q, the chunk length and whether
a mask is present; a route of several members first writes each query
into a zeroed buffer of `route_bucket` rows (`stack_query`), attends it
with one `holder_partial`, and takes each member's rows back
(`take_rows`), so no key grows with the group's composition.

The analytic stage costs ride along unchanged: the returned timeline is
the same schedule the AnalyticBackend produces, so planner parity and
StepStats parity hold by construction.
"""

from __future__ import annotations

import zlib
from collections import defaultdict
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple, \
    TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.chunk_store import ChunkStore
from repro.core.merge import Partial, merge_tree
from repro.core.routing import route_batched
from repro.core.splice import splice_delta_rotate
from repro.models.mla import MLAConfig, absorbed_partial
from repro.obs import phases
from repro.serving.backends.base import StepExecution, StepTicket
from repro.serving.plan import Request, StepPlan, build_timeline

if TYPE_CHECKING:                                    # pragma: no cover
    from repro.serving.engine import ServingEngine


# Execution geometry for CPU-scale tests and the serve CLI: d_qk = 24.
# The PLANNER's costs always use the paper payload (cfg.payload on the
# engine) — primitive decisions are invariant to the execution geometry,
# which is what makes analytic-vs-exec planner parity exact.
TINY_MLA = MLAConfig(d_model=64, n_heads=2, kv_lora_rank=16,
                     qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)

# DeepSeek-V2's published attention widths (config.json of
# deepseek-ai/DeepSeek-V2): 128 heads, kv_lora_rank 512, rope 64, nope 128,
# v_head 128 — so d_qk = 576 and d_v = 512, the paper's wire payload.
DEEPSEEK_V2_MLA = MLAConfig(d_model=5120, n_heads=128, kv_lora_rank=512,
                            q_lora_rank=1536, qk_nope_head_dim=128,
                            qk_rope_head_dim=64, v_head_dim=128)

# execution geometries a caller can name (serve --mla, chip_smoke.py)
MLA_GEOMETRIES = {"tiny": TINY_MLA, "deepseek-v2": DEEPSEEK_V2_MLA}


def oracle_tolerance(dtype) -> float:
    """Largest max|exec - oracle| a correct step may show in `dtype`.

    float32: the exec path and the oracle differ only in the order of f32
    sums, ~1e-6 at unit scale; 1e-4 leaves room for long contractions.

    bfloat16: both sides feed the MXU the same bf16 cache and queries and
    accumulate in f32, but the TPU's default matmul precision rounds the
    f32 softmax weights of the weights @ values dot to bf16, a relative
    error of at most 2^-9 per weight. The exec path normalises weights
    per chunk and the oracle over all of a request's chunks, so the two
    round differently, and an output element can differ by up to
    2^-8 * sum_i p_i |v_i|: 2^-8 times a softmax-weighted mean of |v|,
    which for unit-normal cache entries stays below 4. So 2^-6. Losing
    one of a request's two 2048-token chunks moves some output by ~1."""
    return 2.0 ** -6 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-4


def _stable_seed(*parts) -> int:
    """Deterministic 32-bit seed from stringable parts (NOT Python hash(),
    which is salted per process)."""
    return zlib.crc32(":".join(str(p) for p in parts).encode())


def fetch_source(rec) -> int:
    """The instance a fetch-kind dispatch pulls its bytes FROM — the wire's
    source end. For every fetch-kind record the planner sets link_instance
    to that source: plain "fetch" records carry link_instance == holder,
    and "fetch_replica" spawns carry the canonical holder (their `holder`
    field is the TARGET instance). One shared resolver (ISSUE 7 satellite):
    _exec_fetch and _exec_fetch_selected used to resolve independently —
    link_instance-for-fetch_replica vs always-rec.holder — a divergence
    that delta-0 replication kept silent (every copy holds canonical
    bytes) but that a delta-splice world would surface as wrong bytes."""
    return rec.link_instance if rec.link_instance >= 0 else rec.holder


def chunk_array(cfg: MLAConfig, chunk_id: str, length: int,
                dtype=jnp.float32) -> jax.Array:
    """The canonical c^KV array of a chunk: (length, d_qk), deterministic
    in chunk_id — re-prefill (LOCAL) regenerates exactly these entries."""
    key = jax.random.PRNGKey(_stable_seed("ckv", chunk_id))
    return jax.random.normal(key, (length, cfg.d_qk), dtype)


def query_for(cfg: MLAConfig, rq: Request, step: int,
              dtype=jnp.float32) -> jax.Array:
    """The request's absorbed decode queries this step: (m_q, H, d_qk),
    deterministic in (query_seed, step). The oracle in tests regenerates
    the identical tensor."""
    seed = rq.req_id if rq.query_seed is None else rq.query_seed
    key = jax.random.fold_in(jax.random.PRNGKey(_stable_seed("q", seed)),
                             step)
    return jax.random.normal(key, (rq.m_q, cfg.n_heads, cfg.d_qk), dtype)


def oracle_partial(cfg: MLAConfig, store: ChunkStore, rq: Request,
                   step: int, dtype=jnp.float32) -> Partial:
    """The §3.3 exactness reference: single-instance attention over the
    request's CONCATENATED chunks (canonical arrays, same query tensor the
    backend materialized). Every exec-backend consumer (tests, benchmarks,
    the serve CLI's --verify, examples) checks against THIS — one oracle,
    so query/chunk materialization can never silently diverge from it."""
    q = query_for(cfg, rq, step, dtype)
    cat = jnp.concatenate([store.lookup(c).data for c in rq.chunk_ids],
                          axis=0)
    return absorbed_partial(cfg, q, cat)


def selection_oracle_partial(cfg: MLAConfig, store: ChunkStore, rq: Request,
                             sel, step: int, dtype=jnp.float32) -> Partial:
    """The selection-regime exactness reference: single-instance
    selection_k decode — the DSA path of models/model.py lifted to the
    serving cache. One instance holds the request's CONCATENATED chunks,
    applies the GLOBAL selection mask (sel: a RequestSelection), and
    attends the chosen entries in place (canonical positions — no
    re-rotation, §3.3). The scheduler-driven scatter-attend must reproduce
    this to float round-off regardless of how the selection was split
    across holders or which primitives served the shards."""
    q = query_for(cfg, rq, step, dtype)
    cat = jnp.concatenate([store.lookup(c).data for c in rq.chunk_ids],
                          axis=0)
    gmask = np.concatenate([np.asarray(sel.masks[c]) for c in rq.chunk_ids])
    return absorbed_partial(cfg, q, cat, jnp.asarray(gmask))


def max_oracle_err(engine: "ServingEngine", reqs: List[Request],
                   step: int) -> float:
    """Worst |exec output - oracle| over a step's requests. The engine
    must be running a JaxExecBackend (its cfg/dtype define the oracle).
    Requests under an active selection verify against the selection
    oracle; everything else against dense single-instance attention."""
    backend = engine.backend
    outs = engine.outputs_of(step)
    sels = (engine.plans[step - 1].selections
            if 1 <= step <= len(engine.plans) else {})
    worst = 0.0
    for rq in reqs:
        sel = sels.get(rq.req_id)
        want = (selection_oracle_partial(backend.cfg, engine.store, rq, sel,
                                         step, backend.dtype)
                if sel is not None else
                oracle_partial(backend.cfg, engine.store, rq, step,
                               backend.dtype))
        worst = max(worst, float(jnp.max(
            jnp.abs(outs[rq.req_id].o - want.o))))
    return worst


# the fewest rows a multi-member route's stacked query buffer has: one
# bf16 sublane tile
MIN_ROUTE_ROWS = 16


def route_bucket(rows: int) -> int:
    """Rows of the buffer a route group of `rows` stacked query rows runs
    in: the next power of two, at least MIN_ROUTE_ROWS. Padded rows are
    zero queries whose results are dropped."""
    return max(MIN_ROUTE_ROWS, 1 << (rows - 1).bit_length())


class HolderPrograms(NamedTuple):
    """The compiled programs of the holder partials, named so that a
    profile shows holder compute as `jit_holder_partial`.

    partial(q, ckv, mask=None, *, routed=False) -> Partial: one holder's
      absorbed partial; `routed` traces it as core.routing.route_batched
      of one group on one holder (the same math), as a ROUTE dispatch is.
    stack(buf, q, row) -> buf with q written at `row` (a traced offset).
    take(part, row, *, rows) -> the `rows` rows of `part` from `row`."""
    partial: Callable
    stack: Callable
    take: Callable


def holder_programs(cfg: MLAConfig) -> HolderPrograms:
    """Build the holder-partial programs for `cfg`. Each backend builds
    its own: a program is traced when the backend first calls it with a
    new shape, from the functions this module names at that time."""

    def holder_partial(q, ckv, mask=None, *, routed=False) -> Partial:
        if routed:
            masks = None if mask is None else [[mask]]
            return route_batched(cfg, [q], [[ckv]], masks)[0]
        return absorbed_partial(cfg, q, ckv, mask)

    def stack_query(buf, q, row):
        return lax.dynamic_update_slice_in_dim(buf, q, row, axis=0)

    def take_rows(part, row, *, rows) -> Partial:
        return Partial(*(lax.dynamic_slice_in_dim(x, row, rows, axis=0)
                         for x in part))

    return HolderPrograms(
        partial=jax.jit(holder_partial, static_argnames="routed"),
        stack=jax.jit(stack_query),
        take=jax.jit(take_rows, static_argnames="rows"))


class JaxExecBackend:
    """Execute StepPlans on real arrays. cfg sets the EXECUTION geometry
    (array shapes); it is independent of the planner's cost payload."""

    name = "exec"

    def __init__(self, cfg: MLAConfig = TINY_MLA, dtype=jnp.float32):
        self.cfg = cfg
        self.dtype = dtype
        # query memo (ISSUE 8 satellite): query_for is deterministic in
        # (seed, step, m_q), so the tensor is materialized ONCE per step
        # at the backend level instead of per-execute()-closure — shared
        # by every subclass (shard_map inherits). Entries older than the
        # previous step are pruned when a new step arrives.
        self._qmemo: Dict[Tuple[int, int, int], jax.Array] = {}
        self._qmemo_step = -1
        # the holder-partial programs, built per backend, and the distinct
        # (program, shapes) keys they have been called with
        self._programs = holder_programs(cfg)
        self._program_keys: set = set()
        self._zero_rows: Dict[int, jax.Array] = {}

    @property
    def partial_programs(self) -> int:
        """Distinct holder-partial programs (stack and take helpers
        included) this backend has built: one per key called."""
        return len(self._program_keys)

    def holder_partial(self, q: jax.Array, ckv: jax.Array,
                       mask: Optional[jax.Array] = None, *,
                       routed: bool = False) -> Partial:
        """One holder's partial as one compiled program, keyed by the
        query's rows, the chunk's length and whether a mask is present."""
        self._program_keys.add(("holder_partial", routed, q.shape,
                                ckv.shape, mask is not None))
        return self._programs.partial(q, ckv, mask, routed=routed)

    def query_of(self, rq: Request, step: int) -> jax.Array:
        """Memoized query_for: the request's decode queries this step. A
        miss is one `query` phase."""
        if step != self._qmemo_step:
            if step > self._qmemo_step:
                self._qmemo = {k: v for k, v in self._qmemo.items()
                               if k[1] >= step - 1}
            else:                        # a fresh engine restarted the clock
                self._qmemo.clear()
            self._qmemo_step = step
        seed = rq.req_id if rq.query_seed is None else rq.query_seed
        key = (seed, step, rq.m_q)
        q = self._qmemo.get(key)
        if q is None:
            with phases.phase("query", step, req=rq.req_id):
                q = self._qmemo[key] = query_for(self.cfg, rq, step,
                                                 self.dtype)
        return q

    # -- materialization ----------------------------------------------------

    def ensure_chunk_data(self, store: ChunkStore,
                          chunk_id: str) -> jax.Array:
        """Canonical array of chunk_id, materializing it on first touch."""
        chunk = store.lookup(chunk_id)
        if chunk.data is None:
            store.attach_data(
                chunk_id, chunk_array(self.cfg, chunk_id, chunk.length,
                                      self.dtype))
        return chunk.data

    def _array_on(self, store: ChunkStore, chunk_id: str,
                  instance: int) -> jax.Array:
        """The copy instance would attend: its replica array if the exec
        path produced one, else the canonical array (replicas created
        outside the exec path — e.g. hand-seeded in examples — fall back
        to canonical bytes, which is what a real pull would deliver)."""
        arr = store.array_on(chunk_id, instance)
        return arr if arr is not None else self.ensure_chunk_data(store,
                                                                  chunk_id)

    # -- execution ----------------------------------------------------------

    def execute(self, engine: "ServingEngine",
                plan: StepPlan) -> StepExecution:
        store = engine.store
        reqs: Dict[int, Request] = {rq.req_id: rq for rq in plan.requests}
        sels = plan.selections

        def q_of(rid: int) -> jax.Array:
            return self.query_of(reqs[rid], plan.step)

        def mask_of(rid: int, chunk_id: str) -> Optional[jax.Array]:
            """The indexer's (c_t,) token mask for this access, or None in
            the dense regime (plan.selections is the §5.4 handoff)."""
            sel = sels.get(rid)
            if sel is None:
                return None
            return jnp.asarray(np.asarray(sel.masks[chunk_id]))

        parts: Dict[int, List[Partial]] = defaultdict(list)
        step = plan.step

        # resident accesses: local attention on the instance's copy,
        # through the selection mask when the indexer chose for this request
        for rp in plan.resident_pairs:
            with phases.phase("resident", step, req=rp.req_id,
                              holder=rp.instance):
                arr = self._array_on(store, rp.chunk_id, rp.instance)
                parts[rp.req_id].append(
                    self.holder_partial(q_of(rp.req_id), arr,
                                        mask_of(rp.req_id, rp.chunk_id)))

        for rec in plan.records:
            if rec.backup or not rec.req_ids:
                continue
            if rec.primitive == "route":
                with phases.phase("route", step, holder=rec.holder):
                    self._exec_route(store, rec, q_of, parts, mask_of)
            elif rec.primitive in ("fetch", "fetch_replica"):
                with phases.phase("fetch", step, holder=rec.holder):
                    if rec.req_ids[0] in sels:
                        self._exec_fetch_selected(store, rec, q_of, parts,
                                                  sels[rec.req_ids[0]])
                    else:
                        self._exec_fetch(store, rec, q_of, parts)
            else:                                     # local re-prefill
                with phases.phase("local", step, holder=rec.holder):
                    arr = self.ensure_chunk_data(store, rec.chunk_id)
                    for rid in rec.req_ids:
                        parts[rid].append(
                            self.holder_partial(q_of(rid), arr,
                                                mask_of(rid, rec.chunk_id)))

        outputs = {}
        for rid, ps in parts.items():
            with phases.phase("merge", step, req=rid):
                outputs[rid] = merge_tree(ps)
        return StepExecution(timeline=build_timeline(plan.records),
                             outputs=outputs, backend=self.name)

    # single-process execution blocks as it goes — there is no deferred
    # device barrier to move, so submit runs the step eagerly (ISSUE 10;
    # the shard_map subclass overrides both halves with a real split)

    def submit(self, engine: "ServingEngine", plan: StepPlan) -> StepTicket:
        return StepTicket(plan=plan, execution=self.execute(engine, plan))

    def await_result(self, engine: "ServingEngine",
                     ticket: StepTicket) -> StepExecution:
        return ticket.execution

    def _exec_route(self, store: ChunkStore, rec, q_of, parts,
                    mask_of) -> None:
        """One batched dispatch: one holder-side partial over the holder's
        resident copy for the whole group. A one-member group attends its
        own query; a larger group stacks its queries into a zeroed buffer
        of route_bucket(rows) rows, attends the buffer, and takes each
        member's rows back. A selection-regime dispatch (single-request by
        construction) routes as a MASKED partial — the holder attends
        selected & resident in place (§5.4), the block-sparse shape
        kernels/sparse_select computes."""
        holder_arr = self._array_on(store, rec.chunk_id, rec.holder)
        if len(rec.req_ids) == 1:
            rid = rec.req_ids[0]
            parts[rid].append(self.holder_partial(
                q_of(rid), holder_arr, mask_of(rid, rec.chunk_id),
                routed=True))
            return
        qs = [q_of(rid) for rid in rec.req_ids]
        rows = [q.shape[0] for q in qs]
        bucket = route_bucket(sum(rows))
        progs, keys = self._programs, self._program_keys
        buf = self._zero_rows.get(bucket)
        if buf is None:           # made once per bucket; programs copy it
            buf = self._zero_rows[bucket] = jnp.zeros(
                (bucket,) + qs[0].shape[1:], qs[0].dtype)
        off = 0
        for q, n in zip(qs, rows):
            keys.add(("stack_query", bucket, n))
            buf = progs.stack(buf, q, off)
            off += n
        merged = self.holder_partial(buf, holder_arr, routed=True)
        off = 0
        for rid, n in zip(rec.req_ids, rows):
            keys.add(("take_rows", bucket, n))
            parts[rid].append(progs.take(merged, off, rows=n))
            off += n

    def _exec_fetch(self, store: ChunkStore, rec, q_of, parts) -> None:
        """Move the cache: pull the source copy, delta-0 splice (identity
        rotation — the §6.3 true-prefix re-home our store models), persist
        the replica array where the planner made it resident, then serve
        the group with LOCAL attention on the moved copy."""
        src_arr = self._array_on(store, rec.chunk_id, fetch_source(rec))
        moved = splice_delta_rotate(src_arr, 0, self.cfg)
        dest = rec.home
        if dest >= 0 and store.resident_on(rec.chunk_id, dest):
            store.set_replica_data(rec.chunk_id, dest, moved)
            # the index SIDECAR moves with the cache bytes: keys derive
            # from the latent band only (position-invariant — the splice
            # touches just the rope band), so the replica's keys are the
            # canonical ones when they have been materialized
            keys = store.lookup(rec.chunk_id).index_keys
            if keys is not None:
                store.set_replica_index_keys(rec.chunk_id, dest, keys)
        for rid in rec.req_ids:
            parts[rid].append(self.holder_partial(q_of(rid), moved))

    def _exec_fetch_selected(self, store: ChunkStore, rec, q_of, parts,
                             sel) -> None:
        """FETCH under selection: the scattered gather (§5.4) — pull ONLY
        the selected entries from the holder's copy, at their canonical
        positions (NO splice: re-rotating a selection diverges, see
        core/splice), attend them at the requester, persist nothing (the
        selection is re-chosen every step). Single-process form of
        core.splice.fetch_scattered_gather + local attend."""
        # fetch_replica-under-selection is unreachable by construction:
        # replica spawns batch only DENSE fan-in overflow (selection pairs
        # group per-request, srid >= 0, and never join a dense group), so a
        # selected request can never ride a fetch_replica record. Pinned
        # here so the source resolution below (fetch_source == rec.holder
        # for plain fetch records) cannot silently diverge again.
        assert rec.primitive == "fetch", (
            f"selection fetch arrived as {rec.primitive!r}: replica spawns "
            "must never batch selected requests")
        rid = rec.req_ids[0]
        idx = np.nonzero(np.asarray(sel.masks[rec.chunk_id]))[0]
        if idx.size == 0:
            # the indexer chose nothing on this holder: the gather is
            # empty and the request's partial is the merge identity
            q = q_of(rid)
            parts[rid].append(Partial.identity(
                q.shape[:-1], self.cfg.kv_lora_rank))
            return
        src_arr = self._array_on(store, rec.chunk_id, fetch_source(rec))
        gathered = jnp.take(src_arr, jnp.asarray(idx), axis=0)
        parts[rid].append(self.holder_partial(q_of(rid), gathered))
