"""The distributed indexer service (§5.4 tentpole, ISSUE 4): score ->
select -> scatter-attend, through the scheduler.

Per decode step, for every request in the selection regime:

  score  — the requester derives a NARROW indexer query from its absorbed
           decode rows (the DSA rule of models/model.py's decode path:
           mean-over-heads of the leading d_index latent columns) and
           broadcasts it to every holder of the request's chunks; each
           holder scores its RESIDENT index keys (the chunk store's
           sidecar, materialized alongside c^KV) — index_scores is a
           rank-d_index dot, noise next to the attention compute.
  select — each holder pools scores over the request's query rows, takes a
           LOCAL top-k at NSA 64-token block granularity (padded tail —
           core.selection.block_scores), and returns (block, score)
           candidates; the requester merges them into the GLOBAL top-k.
           Because every holder keeps its k best under one strict total
           order (score desc, then chunk order, then block id), the merged
           set equals the single-instance top-k over the concatenated
           cache — the distributed form is exact, not approximate.
  scatter-attend — the resulting per-(request, holder) masks
           (RequestSelection.masks, the residency_split of the global
           choice) ride the StepPlan into the backends: the exec backend
           attends selected & resident in place and merges partials.

Everything here is host-side control plane on small arrays: scoring runs
in numpy (deterministic, trace-recordable); jax appears only to
materialize the canonical chunk arrays the index keys derive from (the
same deterministic materialization the exec backend uses).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple, TYPE_CHECKING

import numpy as np

from repro.core import constants as C
from repro.core import selection as SEL
from repro.core.chunk_store import ChunkStore
from repro.models.mla import MLAConfig
from repro.serving.backends.jax_exec import TINY_MLA, chunk_array, query_for
from repro.serving.plan import Request
from repro.serving.selection.types import RequestSelection, token_mask

if TYPE_CHECKING:                                    # pragma: no cover
    from repro.serving.engine import ServingEngine


def pooled_max(scores: np.ndarray) -> np.ndarray:
    """Pool index scores over a request's query rows: max — a token ANY
    row wants is kept. (S,) from (m_q, S)."""
    return np.asarray(scores).max(axis=0)


@dataclasses.dataclass(frozen=True)
class SelectionConfig:
    block_tokens: int = C.NSA_BLOCK_TOKENS          # NSA granularity (64)
    # scoring-projection width; None -> the full latent band (d_c), which
    # is exactly the parameter-free rule models/model.py decodes with
    d_index: Optional[int] = None


class IndexerService:
    """The live scoring service. mla fixes the EXECUTION geometry (must
    match the engine's JaxExecBackend so indexer queries and index keys
    derive from the same tensors the backend attends with); the planner's
    cost payload is independent, as everywhere else."""

    name = "indexer"

    def __init__(self, cfg: SelectionConfig = SelectionConfig(),
                 mla: MLAConfig = TINY_MLA, dtype=None):
        import jax.numpy as jnp
        self.cfg = cfg
        self.mla = mla
        self.dtype = jnp.float32 if dtype is None else dtype
        self.block_tokens = cfg.block_tokens
        self.d_index = cfg.d_index or mla.kv_lora_rank
        # every verdict, by engine step — the recordable selection trace
        # (repro.serving.selection.replay.save_selection_trace)
        self.log: Dict[int, Dict[int, RequestSelection]] = {}
        # service telemetry (ISSUE 9), read by the obs metrics registry:
        # roundtrips = per-(request, chunk) scoring round trips, merges =
        # requester-side global merges, merge_candidates / merge_selected =
        # cumulative candidate-in / block-out volumes of those merges.
        self.obs_counts: Dict[str, int] = {
            "roundtrips": 0, "merges": 0,
            "merge_candidates": 0, "merge_selected": 0}
        # per-merge candidate-set sizes since the last drain — bounded by
        # the obs layer draining every step into a streaming histogram
        self._merge_sizes: List[int] = []

    def drain_merge_sizes(self) -> List[int]:
        """Per-merge candidate counts accumulated since the last call
        (the obs layer folds them into a histogram once per step)."""
        out = self._merge_sizes
        self._merge_sizes = []
        return out

    # -- sidecar materialization --------------------------------------------

    def ensure_index_keys(self, store: ChunkStore,
                          chunk_id: str) -> np.ndarray:
        """The chunk's index keys, materializing the sidecar on first
        touch: the leading d_index latent columns of the canonical c^KV
        entries (core.selection.latent_index_keys — position-invariant, so
        replicas carry byte-identical keys). Kept as numpy: scoring is
        host-side control plane."""
        chunk = store.lookup(chunk_id)
        if chunk.index_keys is None:
            src = chunk.data
            if src is None:
                # analytic engines never materialize c^KV; derive the keys
                # from the same deterministic array exec would attend
                src = chunk_array(self.mla, chunk_id, chunk.length,
                                  self.dtype)
            store.attach_index_keys(chunk_id, np.asarray(
                SEL.latent_index_keys(src, self.d_index), np.float32))
        return np.asarray(chunk.index_keys)

    # -- scoring ------------------------------------------------------------

    def index_query(self, rq: Request, step: int) -> np.ndarray:
        """The request's narrow indexer query rows (m_q, d_index): mean
        over heads of the latent band of the SAME absorbed decode queries
        the exec backend materializes (query_for) — the DSA scoring rule of
        models/model.py, so single-instance selection_k decode is the
        oracle this service must reproduce."""
        q = np.asarray(query_for(self.mla, rq, step, self.dtype), np.float32)
        return q[..., :self.d_index].mean(axis=1)

    def pooled_scores(self, store: ChunkStore, rq: Request, iq: np.ndarray,
                      chunk_id: str, step: int) -> np.ndarray:
        """One holder's scoring round: index_scores over the chunk's
        resident keys, max-pooled over the request's query rows (a token
        any row wants is kept) -> (S,). THE distributed hook: the mesh
        service (ShardMapIndexerService) overrides exactly this — the
        candidate policy downstream (topk_from_pooled, _merge) is shared,
        so the two services can only differ in where scores computed."""
        keys = self.ensure_index_keys(store, chunk_id)
        scores = iq @ keys.T                       # (m_q, S) index_scores
        return pooled_max(scores)

    def topk_from_pooled(self, pooled: np.ndarray,
                         k_blocks: int) -> List[Tuple[int, float]]:
        """Aggregate pooled token scores per NSA block (padded tail) and
        return the local top-k (block id, score) candidates under the
        strict total order — score desc, ties toward the lower id."""
        bs = SEL.block_scores(pooled, self.block_tokens)
        k = min(k_blocks, bs.shape[-1])
        order = np.lexsort((np.arange(bs.shape[-1]), -bs))[:k]
        return [(int(b), float(bs[b])) for b in order]

    def local_topk(self, iq: np.ndarray, keys: np.ndarray,
                   k_blocks: int) -> List[Tuple[int, float]]:
        """One holder's side of the service: score + pool + per-block
        top-k. Kept as the single-array entry (tests, examples); the
        service pipeline goes through pooled_scores/topk_from_pooled."""
        return self.topk_from_pooled(pooled_max(iq @ keys.T), k_blocks)

    # -- selection ----------------------------------------------------------

    def _merge(self, rq: Request, per_chunk: Dict[str, list],
               k_blocks: int) -> RequestSelection:
        """Requester-side merge: global top-k over every holder's
        candidates under the strict total order (score desc, chunk
        position, block id) — the same order a single instance ranking
        every block of the concatenated cache would use, so distributed ==
        global (tests assert it; ties cannot diverge, the order is total)."""
        cands = []
        for pos, cid in enumerate(rq.chunk_ids):
            for b, s in per_chunk[cid]:
                cands.append((-s, pos, b))
        cands.sort()
        chosen = cands[:k_blocks]
        self.obs_counts["merges"] += 1
        self.obs_counts["merge_candidates"] += len(cands)
        self.obs_counts["merge_selected"] += len(chosen)
        self._merge_sizes.append(len(cands))
        blocks: Dict[str, Tuple[int, ...]] = {cid: () for cid in rq.chunk_ids}
        for _, pos, b in chosen:
            cid = rq.chunk_ids[pos]
            blocks[cid] = blocks[cid] + (b,)
        blocks = {cid: tuple(sorted(bs)) for cid, bs in blocks.items()}
        # masks need chunk lengths; the callers attach them from the store
        return RequestSelection(rq.req_id, self.block_tokens, blocks, {})

    def _select(self, store: ChunkStore, rq: Request, step: int,
                truncate_local: bool) -> RequestSelection:
        """The one score -> local top-k -> merge -> mask pipeline.
        truncate_local=True is the distributed service (each holder
        returns at most k_blocks candidates); False ranks EVERY block —
        the single-instance reference. Both share this body so the
        distributed==global theorem compares selection POLICY, not two
        drifting implementations."""
        k_blocks = max(1, -(-int(rq.k_selected) // self.block_tokens))
        iq = self.index_query(rq, step)
        per_chunk = {}
        for cid in rq.chunk_ids:
            length = store.lookup(cid).length
            k = (k_blocks if truncate_local
                 else -(-length // self.block_tokens))
            self.obs_counts["roundtrips"] += 1
            pooled = self.pooled_scores(store, rq, iq, cid, step)
            per_chunk[cid] = self.topk_from_pooled(pooled, k)
        sel = self._merge(rq, per_chunk, k_blocks)
        masks = {cid: token_mask(sel.blocks[cid], self.block_tokens,
                                 store.lookup(cid).length)
                 for cid in rq.chunk_ids}
        return dataclasses.replace(sel, masks=masks)

    def select_request(self, store: ChunkStore, rq: Request,
                       step: int) -> RequestSelection:
        """score -> local top-k per holder -> global merge for one
        request. k_blocks = ceil(budget / block_tokens): NSA granularity
        rounds the token budget up to whole blocks."""
        return self._select(store, rq, step, truncate_local=True)

    def global_select(self, store: ChunkStore, rq: Request,
                      step: int) -> RequestSelection:
        """The single-instance reference selection: every block of every
        chunk ranked at once (no per-holder truncation). select_request
        must return exactly this — the distributed-top-k theorem the tests
        pin down."""
        return self._select(store, rq, step, truncate_local=False)

    # -- the engine's entry point -------------------------------------------

    def select_step(self, engine: "ServingEngine", requests: List[Request],
                    step: int) -> Dict[int, RequestSelection]:
        out = {rq.req_id: self.select_request(engine.store, rq, step)
               for rq in requests}
        self.log[step] = out
        return out


class ShardMapIndexerService(IndexerService):
    """The scoring round trip as a REAL mesh collective (ISSUE 7): the
    requester's narrow indexer query rides an all_gather across the
    "instance" axis, the HOLDER shard scores its resident keys and pools
    locally, and only the (S,) pooled scores come back off the mesh. The
    candidate policy (block top-k, global merge) is byte-for-byte the
    inherited IndexerService code — only WHERE scores compute moved, so
    verdicts match the host service and the distributed==global theorem
    carries over unchanged.

    Each scoring call's wall time accumulates in measured_index_s keyed
    (step, req_id, chunk_id); the shard_map exec backend folds it into the
    dispatch's measured "index" stage (the plan prices the indexer round
    trip as part of selection transport)."""

    name = "indexer-shard_map"

    def __init__(self, cfg: SelectionConfig = SelectionConfig(),
                 mla: MLAConfig = TINY_MLA, dtype=None):
        super().__init__(cfg, mla, dtype)
        self.measured_index_s: Dict[Tuple[int, int, str], float] = {}
        self._jits: Dict[tuple, object] = {}

    def pooled_scores(self, store: ChunkStore, rq: Request, iq: np.ndarray,
                      chunk_id: str, step: int) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from repro.serving.backends import shard_map as SM

        keys = self.ensure_index_keys(store, chunk_id)
        holder = store.lookup(chunk_id).holder
        home = rq.home
        mesh, _devices = SM.mesh_for(store.n_instances)
        asm = SM.assembler_for(store.n_instances)
        iq32 = np.asarray(iq, np.float32)
        iq_g = asm.stack({home: iq32}, iq32.shape, jnp.float32)
        keys_g = asm.stack({holder: np.asarray(keys, np.float32)},
                           keys.shape, jnp.float32)
        PS = P(SM.AXIS)

        def build():
            def body(iq_l, keys_l):
                all_iq = lax.all_gather(iq_l, SM.AXIS)    # (NI, m_q, d)
                scores = jnp.einsum("md,sd->ms", all_iq[home], keys_l)
                return scores.max(axis=0)                 # (S,) pooled
            return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(PS, PS),
                                         out_specs=PS))

        cache_key = ("pooled", home, holder,
                     tuple(iq32.shape), tuple(keys.shape))
        pooled_g, dt = SM.staged_call(self._jits, cache_key, build,
                                      (iq_g, keys_g))
        tk = (step, rq.req_id, chunk_id)
        self.measured_index_s[tk] = self.measured_index_s.get(tk, 0.0) + dt
        return np.asarray(asm.take(pooled_g, holder))
