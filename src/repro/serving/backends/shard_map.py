"""ShardMapExecBackend: run the plan on a real device mesh (ISSUE 7).

The chunk store's canonical arrays partition across a mesh axis named
"instance" — one device per serving instance (forced host devices in CI:
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) — and every
transport the planner decided executes as a REAL collective inside
shard_map:

* ROUTE  — the staged core.routing decomposition: ``pairwise_ship`` /
  ``pairwise_return`` ppermutes when the dispatch group shares one home,
  ``fanout_gather`` / ``fanout_exchange`` all-collectives when requesters
  span homes. The query crosses the axis; the cache never does.
* FETCH  — ``core.splice.fetch_chunk`` (bulk ppermute + delta-0 splice;
  the copy persists as the replica array exactly where the planner made
  it resident) or ``fetch_scattered_gather`` under an active selection
  (canonical positions, nothing persisted — §5.4).
* LOCAL  — re-prefill on the requester's own device.

Outputs reproduce the single-instance oracles to float round-off — the
§3.3 exactness claim, now through the scheduler AND a real mesh.

Each wire / compute stage is timed around its collective (jit-compiled
once per shape, warmed before timing so compile never pollutes a sample)
and the measured durations are rebound to the SAME flow structure the
cost model priced; ``timeline.measured_vs_analytic`` re-schedules them
into a measured-vs-analytic MeasuredReport per step — the paper's §7
model-validation loop, continuously exercised in CI. The returned
*analytic* timeline is byte-identical to AnalyticBackend's, so planner
StepStats parity holds by construction (sched_wall_s excepted).

Two execution modes (ISSUE 8):

* ``fused=True`` (default) — each dispatch group's staged chain compiles
  into ONE jitted program per (primitive, shape-signature), every
  record's host->device stacking batches into a single ``device_put``
  per step, all groups launch WITHOUT intermediate ``block_until_ready``
  (JAX async dispatch pipelines them the way the overlap timeline
  models) and the step blocks once at a barrier. Each group's measured
  wall — net of queueing behind groups that share a (link, fabric) wire
  or an SM, per the plan's resource bindings — is apportioned over the
  record's planned stage ratios, so the per-stage measured breakdown
  survives fusion. Merges run on-device over committed shards (every
  partial of a request lands on its home device); nothing round-trips
  through the host until the store persists a replica.
* ``fused=False`` — the PR-7 per-stage path: one timed ``staged_call``
  per stage, host-side merges. The A/B kill switch (mirrors
  ``EngineConfig.vectorized_plan``) and the serial baseline
  ``bench_serving_steadystate --exec-bench`` compares against.
"""

from __future__ import annotations

import sys
import time
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional, Tuple, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.chunk_store import ChunkStore
from repro.core.merge import NEG_INF, Partial, merge_stacked, merge_tree
from repro.core.routing import (check_route_shards, fanout_exchange,
                                fanout_gather, pairwise_return, pairwise_ship)
from repro.core.splice import (fetch_chunk, fetch_scattered_gather,
                               splice_delta_rotate)
from repro.models.mla import MLAConfig, absorbed_partial
from repro.serving import timeline as TL
from repro.serving.backends.base import StepExecution, StepTicket
from repro.serving.backends.jax_exec import (JaxExecBackend, TINY_MLA,
                                             fetch_source)
from repro.serving.plan import StepPlan, build_timeline

if TYPE_CHECKING:                                    # pragma: no cover
    from repro.serving.engine import ServingEngine

AXIS = "instance"

_MESH_CACHE: Dict[int, Tuple[Any, Tuple[Any, ...]]] = {}
_ASM_CACHE: Dict[int, "_ShardAssembler"] = {}


def mesh_for(n_instances: int):
    """A 1-D mesh over the first n_instances devices, axis named AXIS.
    Device order pins instance i to jax.devices()[i], so shard extraction
    by instance index is deterministic."""
    cached = _MESH_CACHE.get(n_instances)
    if cached is not None:
        return cached
    devs = jax.devices()
    if len(devs) < n_instances:
        raise RuntimeError(
            f"shard_map backend needs {n_instances} devices for the "
            f"{AXIS!r} mesh axis but jax sees {len(devs)}. On CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_instances} BEFORE importing jax.")
    devices = tuple(devs[:n_instances])
    mesh = jax.sharding.Mesh(np.asarray(devices), (AXIS,))
    _MESH_CACHE[n_instances] = (mesh, devices)
    return mesh, devices


def assembler_for(n_instances: int) -> "_ShardAssembler":
    asm = _ASM_CACHE.get(n_instances)
    if asm is None:
        asm = _ASM_CACHE[n_instances] = _ShardAssembler(*mesh_for(n_instances))
    return asm


def check_instance_shards(parts: Dict[int, Any], per_shape: Tuple[int, ...],
                          n_instances: Optional[int] = None,
                          axis: str = AXIS) -> None:
    """Up-front per-instance shard validation (ISSUE 7 satellite): every
    supplied shard must match the mesh-wide per-shard shape. A ragged
    shard used to surface only as an opaque XLA concatenation / layout
    error at assembly; shapes are host-side constants here, so the
    mismatch is rejected naming the axis, the offending shard and BOTH
    shapes."""
    per = tuple(per_shape)
    for inst, part in parts.items():
        if n_instances is not None and not 0 <= inst < n_instances:
            raise ValueError(
                f"instance shard on mesh axis {axis!r}: shard {inst} is "
                f"outside the mesh (axis size {n_instances})")
        got = tuple(part.shape)
        if got != per:
            raise ValueError(
                f"instance shards disagree on mesh axis {axis!r}: shard "
                f"{inst} has shape {got} but the mesh-wide per-shard "
                f"shape is {per}")


def staged_call(jits: Dict[Any, Any], key, build, args) -> Tuple[Any, float]:
    """Run a jitted stage and return (output, wall seconds). First call
    per (static, shapes) key builds + WARMS the function — compile time
    never lands in a measured sample; subsequent shapes re-key."""
    fn = jits.get(key)
    if fn is None:
        fn = build()
        jax.block_until_ready(fn(*args))
        jits[key] = fn
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


class _ShardAssembler:
    """Host-side <-> mesh-sharded array plumbing for one mesh size.

    stack() builds a global array sharded P(AXIS) from a {instance:
    per-shard array} dict (absent instances get cached committed zero
    buffers — a non-holder's view of a chunk it does not have); take()
    extracts instance i's committed shard of a global result."""

    def __init__(self, mesh, devices):
        self.mesh = mesh
        self.devices = devices
        self.n = len(devices)
        self._zeros: Dict[Tuple, Any] = {}

    def _zero(self, per_shape: Tuple[int, ...], dtype, inst: int):
        key = (per_shape, jnp.dtype(dtype).name, inst)
        buf = self._zeros.get(key)
        if buf is None:
            buf = jax.device_put(jnp.zeros(per_shape, dtype),
                                 self.devices[inst])
            self._zeros[key] = buf
        return buf

    def stack(self, parts: Dict[int, Any], per_shape: Tuple[int, ...],
              dtype=jnp.float32):
        per_shape = tuple(per_shape)
        check_instance_shards(parts, per_shape, self.n)
        bufs = []
        for inst in range(self.n):
            part = parts.get(inst)
            if part is None:
                bufs.append(self._zero(per_shape, dtype, inst))
            else:
                bufs.append(jax.device_put(jnp.asarray(part, dtype),
                                           self.devices[inst]))
        gshape = (self.n * per_shape[0],) + per_shape[1:]
        return jax.make_array_from_single_device_arrays(
            gshape, NamedSharding(self.mesh, P(AXIS)), bufs)

    def take(self, garr, inst: int):
        """Instance inst's per-shard slice of a P(AXIS)-sharded global
        array, as the committed single-device buffer."""
        per = garr.shape[0] // self.n
        for s in garr.addressable_shards:
            if (s.index[0].start or 0) == inst * per:
                return s.data
        raise RuntimeError(               # pragma: no cover - all host devs
            f"no addressable shard for instance {inst} on axis {AXIS!r}")

    def begin_batch(self) -> "_StackBatch":
        """A deferred-stacking batch: collect a whole step's placements,
        transfer them in ONE device_put (ISSUE 8)."""
        return _StackBatch(self)


class _StackBatch:
    """One step's host->device transfers, batched. add() defers a
    _ShardAssembler.stack(); put() defers a single-device commit; both
    return integer handles into the list commit() produces. commit()
    issues a SINGLE batched jax.device_put over every (array, device)
    pair — one dispatch instead of one per record input — then assembles
    the global sharded arrays from the committed buffers. Transfers can
    dedupe per step via put(key=...): the same query tensor feeding two
    records on one device ships once."""

    def __init__(self, asm: _ShardAssembler):
        self.asm = asm
        self._src: List[Any] = []
        self._dev: List[Any] = []
        self._dedupe: Dict[Any, int] = {}
        self._items: List[Tuple] = []

    def _tx(self, arr, inst: int, key=None) -> int:
        if key is not None:
            hit = self._dedupe.get(key)
            if hit is not None:
                return hit
        slot = len(self._src)
        self._src.append(arr)
        self._dev.append(self.asm.devices[inst])
        if key is not None:
            self._dedupe[key] = slot
        return slot

    def put(self, arr, inst: int, key=None) -> int:
        """Commit one array to instance inst's device."""
        self._items.append(("put", self._tx(jnp.asarray(arr), inst, key)))
        return len(self._items) - 1

    def add(self, parts: Dict[int, Any], per_shape: Tuple[int, ...],
            dtype=jnp.float32) -> int:
        """_ShardAssembler.stack, deferred: absent instances resolve to
        the assembler's cached committed zero buffers at commit()."""
        per_shape = tuple(per_shape)
        check_instance_shards(parts, per_shape, self.asm.n)
        slots: List[Optional[int]] = []
        for inst in range(self.asm.n):
            p = parts.get(inst)
            slots.append(None if p is None
                         else self._tx(jnp.asarray(p, dtype), inst))
        self._items.append(("stack", per_shape, jnp.dtype(dtype), slots))
        return len(self._items) - 1

    def commit(self) -> List[Any]:
        bufs = jax.device_put(self._src, self._dev) if self._src else []
        out: List[Any] = []
        for item in self._items:
            if item[0] == "put":
                out.append(bufs[item[1]])
                continue
            _, per_shape, dtype, slots = item
            shard_bufs = [bufs[s] if s is not None
                          else self.asm._zero(per_shape, dtype, inst)
                          for inst, s in enumerate(slots)]
            gshape = (self.asm.n * per_shape[0],) + per_shape[1:]
            out.append(jax.make_array_from_single_device_arrays(
                gshape, NamedSharding(self.asm.mesh, P(AXIS)), shard_bufs))
        return out


class ShardMapExecBackend(JaxExecBackend):
    """JaxExecBackend semantics on a real mesh, with measured stage
    timings. cfg is the execution geometry (TINY_MLA by default; the
    planner's cost payload is independent — analytic/exec planner parity
    is exact)."""

    name = "shard_map"
    _warned_fill = False               # process-wide warn-once (ISSUE 8)

    def __init__(self, cfg: MLAConfig = TINY_MLA, dtype=jnp.float32,
                 fused: bool = True):
        super().__init__(cfg, dtype)
        self.fused = fused
        self.mesh = None
        self.devices: Tuple[Any, ...] = ()
        self._asm: Optional[_ShardAssembler] = None
        self._jits: Dict[Any, Any] = {}
        self._pool: Dict[Tuple[str, int], Any] = {}
        self._tiny = None
        self._listening_store = None
        self._fill_count = 0
        # per-step / cumulative phase walls of the fused path (stack /
        # dispatch / barrier / merge) — benchmarks/profile_exec.py reads
        # these; four perf_counter probes per step, nothing on the
        # per-record path
        self.phase_wall: Dict[str, float] = {}
        self.phase_wall_total: Dict[str, float] = {}

    # -- mesh binding -------------------------------------------------------

    def _bind(self, engine: "ServingEngine") -> None:
        ni = len(engine.instances)
        if self.mesh is None or len(self.devices) != ni:
            self.mesh, self.devices = mesh_for(ni)
            self._asm = assembler_for(ni)
            self._jits.clear()
            self._pool.clear()
            self._tiny = self._asm.stack({}, (1,), jnp.float32)
        store = engine.store
        if self._listening_store is not store:
            # bound committed-copy cache (ISSUE 8 satellite): when the
            # engine's LRU path retires a replica (or a holder dies), the
            # device-side buffer retires with it
            store.add_evict_listener(self._retire_pooled)
            self._listening_store = store

    def _retire_pooled(self, chunk_id: str, instance: int) -> None:
        self._pool.pop((chunk_id, instance), None)

    def _pool_bytes(self) -> int:
        return sum(int(getattr(b, "nbytes", 0))
                   for b in self._pool.values())

    def _shmap(self, body, in_specs, out_specs):
        return jax.jit(jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs))

    def _staged(self, statics: Tuple, build, args) -> Tuple[Any, float]:
        key = statics + tuple(
            (tuple(x.shape), jnp.dtype(x.dtype).name)
            for x in jax.tree.leaves(args))
        return staged_call(self._jits, key, build, args)

    def _committed_copy(self, store: ChunkStore, chunk_id: str,
                        inst: int):
        """The copy instance `inst` attends, committed to ITS device.
        Cached per (chunk, instance): chunk bytes are canonical under
        delta-0 replication, so a cached copy can never go stale in
        content — only in shape, which re-keys."""
        arr = self._array_on(store, chunk_id, inst)
        key = (chunk_id, inst)
        buf = self._pool.get(key)
        if buf is None or buf.shape != arr.shape:
            buf = jax.device_put(arr, self.devices[inst])
            self._pool[key] = buf
        return buf

    @staticmethod
    def _uncommit(x):
        """Strip device commitment (via host) so downstream host-side
        merges can mix operands from different shards."""
        return jnp.asarray(np.asarray(x))

    # -- execution ----------------------------------------------------------

    def execute(self, engine: "ServingEngine",
                plan: StepPlan) -> StepExecution:
        return self.await_result(engine, self.submit(engine, plan))

    def submit(self, engine: "ServingEngine", plan: StepPlan) -> StepTicket:
        """Issue the step WITHOUT blocking (ISSUE 10): bind, STACK the
        batched device_put, DISPATCH every fused program — everything of
        _execute_overlapped up to (not including) the barrier. The engine
        plans the next step while the devices chew; await_result barriers
        and merges. The serial chain has no deferrable barrier (each
        staged_call blocks), so fused=False stays eager — the A/B oracle
        is a ticket whose execution is already complete."""
        t_wall0 = time.perf_counter()
        self._bind(engine)
        if not self.fused:
            self._fill_count = 0
            return StepTicket(plan=plan, execution=self._execute_serial(
                engine, plan, t_wall0))
        return StepTicket(plan=plan,
                          state=self._submit_overlapped(engine, plan,
                                                        t_wall0))

    def await_result(self, engine: "ServingEngine",
                     ticket: StepTicket) -> StepExecution:
        if ticket.execution is not None:
            return ticket.execution
        return self._await_overlapped(engine, ticket.plan, ticket.state)

    def _analytic_timeline(self, plan: StepPlan):
        """EXACTLY what AnalyticBackend produces, so StepStats derived
        from it are bit-identical (golden parity)."""
        if plan.arrays is not None:
            return TL.simulate_arrays(plan.arrays.flow_arrays())
        return build_timeline(plan.records)

    def _report(self, plan: StepPlan, analytic, measured_flows,
                t_wall0: float, mode: str) -> TL.MeasuredReport:
        return TL.measured_vs_analytic(
            plan.step, analytic, measured_flows,
            time.perf_counter() - t_wall0, mode=mode,
            pool_entries=len(self._pool), pool_bytes=self._pool_bytes(),
            stage_fills=self._fill_count)

    def _execute_serial(self, engine: "ServingEngine", plan: StepPlan,
                        t_wall0: float) -> StepExecution:
        store = engine.store
        reqs = {rq.req_id: rq for rq in plan.requests}
        sels = plan.selections

        def q_of(rid: int) -> jax.Array:
            return self.query_of(reqs[rid], plan.step)

        def mask_of(rid: int, chunk_id: str) -> Optional[np.ndarray]:
            sel = sels.get(rid)
            if sel is None:
                return None
            return np.asarray(sel.masks[chunk_id], bool)

        parts: Dict[int, List[Partial]] = defaultdict(list)

        # resident accesses attend host-side, exactly like the in-process
        # backend: the analytic flow set has no transport for them either,
        # so the measured flow set stays structurally identical.
        for rp in plan.resident_pairs:
            arr = self._array_on(store, rp.chunk_id, rp.instance)
            m = mask_of(rp.req_id, rp.chunk_id)
            parts[rp.req_id].append(
                absorbed_partial(self.cfg, q_of(rp.req_id), arr,
                                 None if m is None else jnp.asarray(m)))

        sel_times = getattr(engine.selector, "measured_index_s", None) or {}
        measured_flows: List[TL.Flow] = []
        for i, rec in enumerate(plan.records):
            if rec.backup or not rec.req_ids:
                continue
            if rec.primitive == "route":
                meas = self._exec_route_mesh(store, rec, q_of, parts,
                                             mask_of, reqs)
            elif rec.primitive in ("fetch", "fetch_replica"):
                if rec.req_ids[0] in sels:
                    meas = self._exec_fetch_selected_mesh(
                        store, rec, q_of, parts, sels[rec.req_ids[0]])
                else:
                    meas = self._exec_fetch_mesh(store, rec, q_of, parts)
            else:
                meas = self._exec_local_mesh(store, rec, q_of, parts,
                                             mask_of)
            if rec.stages and rec.stages[0][0] == "index":
                # the indexer round trip ran at PLAN time (the selector's
                # scoring collective); its measured wall lands here
                meas.setdefault("index", float(sel_times.get(
                    (plan.step, rec.req_ids[0], rec.chunk_id), 0.0)))
            if rec.stages:
                measured_flows.append(self._measured_flow(rec, i, meas))

        outputs = {rid: merge_tree(ps) for rid, ps in parts.items()}
        analytic = self._analytic_timeline(plan)
        report = self._report(plan, analytic, measured_flows, t_wall0,
                              "serial")
        return StepExecution(timeline=analytic, outputs=outputs,
                             backend=self.name, measured=report)

    def _count_fill(self, rec, n: int) -> None:
        """A stage duration had to be invented (a serial stage went
        unmeasured, or a fused wall apportioned over all-zero planned
        durations): count it on the step's MeasuredReport and warn ONCE
        per process — silent 0.0 fills used to deflate measured
        makespans (ISSUE 8 satellite)."""
        self._fill_count += n
        cls = type(self)
        if not cls._warned_fill:
            cls._warned_fill = True
            print(f"[shard_map] warning: filled {n} unmeasured stage "
                  f"duration(s) on {rec.primitive}:{rec.chunk_id}; "
                  f"counted on MeasuredReport.stage_fills (warn-once)",
                  file=sys.stderr)

    def _measured_flow(self, rec, i: int, meas: Dict[str, float]) -> TL.Flow:
        """Rebind the record's planned stage chain to measured durations:
        same key, same stage names/order, same resource binding as
        plan.build_timeline — so the measured schedule is comparable
        stage-for-stage with the analytic one."""
        missing = [name for name, _dur in rec.stages if name not in meas]
        if missing:
            self._count_fill(rec, len(missing))
        stages = [(name, float(meas.get(name, 0.0)))
                  for name, _dur in rec.stages]
        link_res = (TL.link(rec.link_instance, rec.fabric_idx)
                    if rec.link_instance >= 0 else None)
        requester = rec.home if rec.home >= 0 else rec.holder
        return TL.transport_flow(
            f"{rec.primitive}:{rec.chunk_id}@{rec.holder}#{i}", stages,
            link_res=link_res, holder_sm=TL.sm(rec.holder),
            requester_sm=TL.sm(requester), primitive=rec.primitive,
            chunk_id=rec.chunk_id)

    # -- ROUTE --------------------------------------------------------------

    def _exec_route_mesh(self, store, rec, q_of, parts, mask_of,
                         reqs) -> Dict[str, float]:
        holder = rec.holder
        ckv = self._committed_copy(store, rec.chunk_id, holder)
        mask = mask_of(rec.req_ids[0], rec.chunk_id)
        valid = (np.ones(ckv.shape[0], bool) if mask is None else mask)
        qs = [q_of(rid) for rid in rec.req_ids]
        homes = [reqs[rid].home for rid in rec.req_ids]
        for q, home in zip(qs, homes):
            check_route_shards(AXIS, q, ckv, valid, shard=home)
        if len(set(homes)) == 1:
            stacked = jnp.concatenate(qs, axis=0) if len(qs) > 1 else qs[0]
            meas, merged = self._route_pairwise_staged(ckv, valid, stacked,
                                                       holder, homes[0])
            off = 0
            for rid, q in zip(rec.req_ids, qs):
                n = q.shape[0]
                parts[rid].append(Partial(o=merged.o[off:off + n],
                                          m=merged.m[off:off + n],
                                          l=merged.l[off:off + n]))
                off += n
            return meas
        # requesters span homes: the fanout schedule — every home ships
        # its block of rows in ONE all_gather round, padded to the widest
        by_home: Dict[int, List[jax.Array]] = {}
        slices: Dict[int, Tuple[int, int, int]] = {}
        for rid, q, home in zip(rec.req_ids, qs, homes):
            blk = by_home.setdefault(home, [])
            start = sum(x.shape[0] for x in blk)
            blk.append(q)
            slices[rid] = (home, start, q.shape[0])
        b_pad = max(sum(x.shape[0] for x in blk) for blk in by_home.values())
        blocks: Dict[int, jax.Array] = {}
        for home, blk in by_home.items():
            block = jnp.concatenate(blk, axis=0) if len(blk) > 1 else blk[0]
            if block.shape[0] < b_pad:
                pad = jnp.zeros((b_pad - block.shape[0],) + block.shape[1:],
                                block.dtype)
                block = jnp.concatenate([block, pad], axis=0)
            blocks[home] = block
        meas, merged_by_home = self._route_fanout_staged(
            ckv, valid, blocks, b_pad, holder)
        for rid in rec.req_ids:
            home, start, n = slices[rid]
            mp = merged_by_home[home]
            parts[rid].append(Partial(o=mp.o[start:start + n],
                                      m=mp.m[start:start + n],
                                      l=mp.l[start:start + n]))
        return meas

    def _route_pairwise_staged(self, ckv, valid, q_stacked, holder: int,
                               requester: int):
        """ROUTE, one home: probe / transfer / compute / return around the
        staged core.routing ppermute decomposition, merge host-side. Non-
        participant shards see zero queries against all-False masks — the
        merge identity (core.merge NaN-guards pin this)."""
        meas: Dict[str, float] = {}
        PS = P(AXIS)
        PART = Partial(o=PS, m=PS, l=PS)
        _, meas["probe"] = self._staged(
            ("probe-pair", holder, requester),
            lambda: self._shmap(
                lambda t: lax.ppermute(t, AXIS, [(requester, holder)]),
                (PS,), PS),
            (self._tiny,))
        qg = self._asm.stack({requester: q_stacked},
                             tuple(q_stacked.shape), self.dtype)
        shipped, meas["transfer"] = self._staged(
            ("pair-ship", holder, requester),
            lambda: self._shmap(
                lambda q: pairwise_ship(q, holder, requester, AXIS),
                (PS,), PS),
            (qg,))
        cg = self._asm.stack({holder: ckv}, tuple(ckv.shape), self.dtype)
        vg = self._asm.stack({holder: valid}, (valid.shape[0],), jnp.bool_)
        part, meas["compute"] = self._staged(
            ("route-compute", holder),
            lambda: self._shmap(
                lambda q, c, v: absorbed_partial(self.cfg, q, c, v),
                (PS, PS, PS), PART),
            (shipped, cg, vg))
        back, meas["return"] = self._staged(
            ("pair-return", holder, requester),
            lambda: self._shmap(
                lambda p: pairwise_return(p, holder, requester, AXIS),
                (PART,), PART),
            (part,))
        t0 = time.perf_counter()
        merged = Partial(*(self._uncommit(self._asm.take(x, requester))
                           for x in back))
        meas["merge"] = time.perf_counter() - t0
        return meas, merged

    def _route_fanout_staged(self, ckv, valid, blocks: Dict[int, jax.Array],
                             b_pad: int, holder: int):
        """ROUTE, many homes: all_gather the padded query blocks, one
        holder-side batched partial over every visitor, all_to_all the
        partials home, merge_stacked on-shard."""
        meas: Dict[str, float] = {}
        PS = P(AXIS)
        PART = Partial(o=PS, m=PS, l=PS)
        _, meas["probe"] = self._staged(
            ("probe-fan",),
            lambda: self._shmap(lambda t: lax.all_gather(t, AXIS),
                                (PS,), PS),
            (self._tiny,))
        sample = next(iter(blocks.values()))
        qg = self._asm.stack(blocks, (b_pad,) + tuple(sample.shape[1:]),
                             self.dtype)
        gathered, meas["transfer"] = self._staged(
            ("fan-gather",),
            lambda: self._shmap(lambda q: fanout_gather(q, AXIS), (PS,), PS),
            (qg,))
        cg = self._asm.stack({holder: ckv}, tuple(ckv.shape), self.dtype)
        vg = self._asm.stack({holder: valid}, (valid.shape[0],), jnp.bool_)
        part, meas["compute"] = self._staged(
            ("route-compute", holder),
            lambda: self._shmap(
                lambda q, c, v: absorbed_partial(self.cfg, q, c, v),
                (PS, PS, PS), PART),
            (gathered, cg, vg))
        ex, meas["return"] = self._staged(
            ("fan-exchange",),
            lambda: self._shmap(lambda p: fanout_exchange(p, AXIS),
                                (PART,), PART),
            (part,))
        t0 = time.perf_counter()
        merged_g, _dt = self._staged(
            ("fan-merge",),
            lambda: self._shmap(lambda p: merge_stacked(p.o, p.m, p.l),
                                (PART,), PART),
            (ex,))
        merged = {home: Partial(*(self._uncommit(self._asm.take(x, home))
                                  for x in merged_g))
                  for home in blocks}
        meas["merge"] = time.perf_counter() - t0
        return meas, merged

    # -- FETCH --------------------------------------------------------------

    def _exec_fetch_mesh(self, store, rec, q_of, parts) -> Dict[str, float]:
        """Move the cache across the mesh: bulk ppermute pull into the
        destination's pool (core.splice.fetch_chunk, delta elided), delta-0
        splice on the destination shard, persist the replica where the
        planner made it resident, then the group attends locally."""
        meas: Dict[str, float] = {}
        src = fetch_source(rec)
        dst = rec.home if rec.home >= 0 else rec.holder
        ckv = self._committed_copy(store, rec.chunk_id, src)
        PS = P(AXIS)
        cg = self._asm.stack({src: ckv}, tuple(ckv.shape), self.dtype)
        pool_g = self._asm.stack({}, tuple(ckv.shape), self.dtype)
        pulled, meas["pull"] = self._staged(
            ("fetch-pull", src, dst),
            lambda: self._shmap(
                lambda pool, c: fetch_chunk(pool, c, None, 0, self.cfg,
                                            src, dst, AXIS),
                (PS, PS), PS),
            (pool_g, cg))
        moved_dev = self._asm.take(pulled, dst)
        moved_dev, meas["splice"] = self._staged(
            ("splice",),
            lambda: jax.jit(lambda x: splice_delta_rotate(x, 0, self.cfg)),
            (moved_dev,))
        moved = self._uncommit(moved_dev)
        if rec.home >= 0 and store.resident_on(rec.chunk_id, rec.home):
            self._pool[(rec.chunk_id, rec.home)] = moved_dev
            store.set_replica_data(rec.chunk_id, rec.home, moved)
            keys = store.lookup(rec.chunk_id).index_keys
            if keys is not None:
                store.set_replica_index_keys(rec.chunk_id, rec.home, keys)
        for rid in rec.req_ids:
            parts[rid].append(absorbed_partial(self.cfg, q_of(rid), moved))
        return meas

    def _exec_fetch_selected_mesh(self, store, rec, q_of, parts,
                                  sel) -> Dict[str, float]:
        """FETCH under selection: core.splice.fetch_scattered_gather —
        pull ONLY the chosen entries at canonical positions (no splice),
        attend at the requester, persist nothing."""
        assert rec.primitive == "fetch", (
            f"selection fetch arrived as {rec.primitive!r}: replica spawns "
            "must never batch selected requests")
        rid = rec.req_ids[0]
        idx = np.nonzero(np.asarray(sel.masks[rec.chunk_id]))[0]
        if idx.size == 0:
            q = q_of(rid)
            parts[rid].append(Partial.identity(
                q.shape[:-1], self.cfg.kv_lora_rank))
            return {"gather": 0.0}
        src = fetch_source(rec)
        dst = rec.home if rec.home >= 0 else rec.holder
        ckv = self._committed_copy(store, rec.chunk_id, src)
        PS = P(AXIS)
        cg = self._asm.stack({src: ckv}, tuple(ckv.shape), self.dtype)
        pool_g = self._asm.stack({}, (int(idx.size), ckv.shape[1]),
                                 self.dtype)
        pulled, dt = self._staged(
            ("fetch-gather", src, dst),
            lambda: self._shmap(
                lambda pool, c, ix: fetch_scattered_gather(
                    pool, c, ix, 0, self.cfg, src, dst, AXIS),
                (PS, PS, P()), PS),
            (pool_g, cg, jnp.asarray(idx)))
        gathered = self._uncommit(self._asm.take(pulled, dst))
        parts[rid].append(absorbed_partial(self.cfg, q_of(rid), gathered))
        return {"gather": dt}

    # -- LOCAL --------------------------------------------------------------

    def _exec_local_mesh(self, store, rec, q_of, parts,
                         mask_of) -> Dict[str, float]:
        """Re-prefill on the requester's own device (no wire)."""
        arr = self.ensure_chunk_data(store, rec.chunk_id)
        inst = rec.home if rec.home >= 0 else rec.holder
        carr = jax.device_put(arr, self.devices[inst])
        total = 0.0
        for rid in rec.req_ids:
            q = jax.device_put(q_of(rid), self.devices[inst])
            mask = mask_of(rid, rec.chunk_id)
            if mask is None:
                out, dt = self._staged(
                    ("prefill", inst),
                    lambda: jax.jit(
                        lambda q, c: absorbed_partial(self.cfg, q, c)),
                    (q, carr))
            else:
                cm = jax.device_put(jnp.asarray(mask), self.devices[inst])
                out, dt = self._staged(
                    ("prefill-mask", inst),
                    lambda: jax.jit(
                        lambda q, c, v: absorbed_partial(self.cfg, q, c, v)),
                    (q, carr, cm))
            total += dt
            parts[rid].append(jax.tree.map(self._uncommit, out))
        return {"prefill": total}

    # =======================================================================
    # Fused + overlapped execution (ISSUE 8 tentpole). One jitted program
    # per dispatch group, one batched stack per step, async launches, one
    # barrier. Numerically the same staged core.routing / core.splice
    # compositions as the serial path — XLA just sees them in one trace.
    # =======================================================================

    def _fused_fn(self, statics: Tuple, build, args):
        """The cached jitted program for (statics, arg shapes/dtypes).
        First build WARMS it (a blocking call on the real args) so
        compile never pollutes a measured sample; later calls return the
        cached wrapper without touching the device."""
        key = ("fused",) + tuple(statics) + tuple(
            (tuple(x.shape), jnp.dtype(x.dtype).name)
            for x in jax.tree.leaves(args))
        fn = self._jits.get(key)
        if fn is None:
            fn = build()
            jax.block_until_ready(fn(*args))
            self._jits[key] = fn
        return fn

    def _gated_partial(self, holder: int, q, c, v) -> Partial:
        """absorbed_partial on the HOLDER shard only. Every shard of the
        SPMD program traces the compute, but the lax.cond branches at
        runtime on axis_index, so non-holder shards skip the einsum
        entirely. On a real fabric the skip is free (the shards run in
        parallel anyway); on forced host devices — where all shards
        time-share one CPU — it removes an NI-fold redundancy that is
        pure harness artifact: the analytic schedule prices the holder's
        compute once. The skipped value is bitwise what the masked
        compute produces on a zero shard (all-False valid -> -inf
        logits): the merge identity, so fanout merge_stacked semantics
        are unchanged. The identity is built device-invariant; it is cast
        to varying over AXIS so both cond branches carry the computed
        branch's type."""
        aval = jax.eval_shape(
            lambda a, b, d: absorbed_partial(self.cfg, a, b, d), q, c, v)
        ident = Partial(o=jnp.zeros(aval.o.shape, aval.o.dtype),
                        m=jnp.full(aval.m.shape, NEG_INF, aval.m.dtype),
                        l=jnp.zeros(aval.l.shape, aval.l.dtype))
        ident = jax.tree.map(lambda x: lax.pcast(x, AXIS, to="varying"),
                             ident)
        return lax.cond(lax.axis_index(AXIS) == holder,
                        lambda: absorbed_partial(self.cfg, q, c, v),
                        lambda: ident)

    def _route_pair_program(self, holder: int, requester: int):
        """The fused one-home ROUTE program: ship the stacked queries
        requester -> holder, attend on the holder shard, return the
        partial. Arguments (q, c, v) are P(AXIS)-sharded over self.mesh."""
        PS = P(AXIS)

        def body(q, c, v):
            qh = pairwise_ship(q, holder, requester, AXIS)
            p = self._gated_partial(holder, qh, c, v)
            return pairwise_return(p, holder, requester, AXIS)
        return self._shmap(body, (PS, PS, PS), Partial(o=PS, m=PS, l=PS))

    @staticmethod
    def _record_resources(rec) -> List:
        """The plan's resource bindings for one dispatch group — the same
        (link, fabric) wire and SM keys build_timeline binds. Two groups
        sharing any of these are ORDERED on the device; groups sharing
        none are independent and their queue wait must not be billed as
        execution (ISSUE 8 wall attribution)."""
        res: List = []
        if rec.link_instance >= 0:
            res.append(TL.link(rec.link_instance, rec.fabric_idx))
        requester = rec.home if rec.home >= 0 else rec.holder
        res.append(TL.sm(rec.holder))
        if requester != rec.holder:
            res.append(TL.sm(requester))
        return res

    def _apportion(self, rec, wall: float, sel_times,
                   step: int) -> Dict[str, float]:
        """Spread one group's fused measured wall over the record's
        planned stage ratios, so the per-stage measured breakdown
        survives fusion. The "index" stage is excluded from the base —
        its wall was measured at PLAN time by the selector's scoring
        collective. Full coverage of the planned stage list is asserted;
        an all-zero planned base falls back to an even split, counted as
        a fill (ISSUE 8 satellite)."""
        names = [n for n, _ in rec.stages]
        meas: Dict[str, float] = {}
        if "index" in names:
            meas["index"] = float(sel_times.get(
                (step, rec.req_ids[0], rec.chunk_id), 0.0))
        rest = [(n, d) for n, d in rec.stages if n != "index"]
        total = sum(d for _, d in rest)
        if rest:
            if total > 0:
                for n, d in rest:
                    meas[n] = wall * (d / total)
            else:
                self._count_fill(rec, len(rest))
                for n, _ in rest:
                    meas[n] = wall / len(rest)
        assert set(meas) == set(names), \
            (rec.primitive, rec.chunk_id, set(names) ^ set(meas))
        return meas

    def _submit_overlapped(self, engine: "ServingEngine", plan: StepPlan,
                           t_wall0: float) -> dict:
        """STACK + DISPATCH of the fused path (ISSUE 8), detached from the
        barrier (ISSUE 10): returns the launch context _await_overlapped
        finishes. Everything here reads only plan-time state — residency
        was committed by plan_step, replica BYTES a prior in-flight step
        has not persisted yet resolve to canonical bytes via _array_on
        (identical content under delta-0 replication), so a submit issued
        before the previous step's merge is value-equivalent."""
        store = engine.store
        reqs = {rq.req_id: rq for rq in plan.requests}
        sels = plan.selections

        def q_of(rid: int) -> jax.Array:
            return self.query_of(reqs[rid], plan.step)

        def mask_of(rid: int, chunk_id: str) -> Optional[np.ndarray]:
            sel = sels.get(rid)
            if sel is None:
                return None
            return np.asarray(sel.masks[chunk_id], bool)

        parts: Dict[int, List[Partial]] = defaultdict(list)
        for rp in plan.resident_pairs:
            arr = self._array_on(store, rp.chunk_id, rp.instance)
            m = mask_of(rp.req_id, rp.chunk_id)
            parts[rp.req_id].append(
                absorbed_partial(self.cfg, q_of(rp.req_id), arr,
                                 None if m is None else jnp.asarray(m)))

        # -- STACK: collect every record's device inputs, ship them in
        # ONE batched transfer ---------------------------------------------
        t0 = time.perf_counter()
        batch = self._asm.begin_batch()
        preps = []
        for i, rec in enumerate(plan.records):
            if rec.backup or not rec.req_ids:
                continue
            if rec.primitive == "route":
                prep = self._prep_route(store, rec, q_of, reqs, mask_of,
                                        batch)
            elif rec.primitive in ("fetch", "fetch_replica"):
                if rec.req_ids[0] in sels:
                    prep = self._prep_fetch_selected(
                        store, rec, q_of, batch, sels[rec.req_ids[0]])
                else:
                    prep = self._prep_fetch(store, rec, q_of, reqs, batch)
            else:
                prep = self._prep_local(store, rec, q_of, reqs, mask_of,
                                        batch)
            preps.append((i, rec, prep))
        bufs = batch.commit()
        t_stack = time.perf_counter() - t0

        # -- DISPATCH: launch every group's fused program in record order
        # with NO intermediate block — JAX's async dispatch pipelines the
        # launches exactly the way the overlap timeline models ---------------
        t0 = time.perf_counter()
        tasks = []
        for i, rec, (launch, post) in preps:
            t_launch, out = launch(bufs)
            tasks.append([i, rec, out, post, t_launch, 0.0])
        t_dispatch = time.perf_counter() - t0
        return {"parts": parts, "tasks": tasks, "t_wall0": t_wall0,
                "t_stack": t_stack, "t_dispatch": t_dispatch}

    def _await_overlapped(self, engine: "ServingEngine", plan: StepPlan,
                          state: dict) -> StepExecution:
        parts, tasks = state["parts"], state["tasks"]
        t_wall0 = state["t_wall0"]
        # per-step fill counter: fills only ever happen in the merge phase
        # (_apportion/_measured_flow), and the engine drains tickets FIFO
        # in a single thread, so resetting here keeps _report per-step
        # accurate even with several submits in flight
        self._fill_count = 0

        # -- BARRIER: block once per step, in launch order -------------------
        t0 = time.perf_counter()
        for task in tasks:
            jax.block_until_ready(task[2])
            task[5] = time.perf_counter()
        t_barrier = time.perf_counter() - t0

        # -- MERGE/account: attribute walls net of same-resource queueing,
        # apportion over planned stage ratios, splice partials per request,
        # persist replicas (the only host round-trip left) -------------------
        t0 = time.perf_counter()
        sel_times = getattr(engine.selector, "measured_index_s",
                            None) or {}
        measured_flows: List[TL.Flow] = []
        last_done: Dict[Any, float] = {}
        for i, rec, out, post, t_launch, t_done in tasks:
            resources = self._record_resources(rec)
            t_ready = max([t_launch]
                          + [last_done.get(r, 0.0) for r in resources])
            wall = max(t_done - t_ready, 1e-9)
            for r in resources:
                last_done[r] = max(last_done.get(r, 0.0), t_done)
            if rec.stages:
                meas = self._apportion(rec, wall, sel_times, plan.step)
                measured_flows.append(self._measured_flow(rec, i, meas))
            post(out, parts)
        outputs = {rid: merge_tree(ps) for rid, ps in parts.items()}
        analytic = self._analytic_timeline(plan)
        report = self._report(plan, analytic, measured_flows, t_wall0,
                              "fused")
        self.phase_wall = {"stack": state["t_stack"],
                           "dispatch": state["t_dispatch"],
                           "barrier": t_barrier,
                           "merge": time.perf_counter() - t0}
        for k, v in self.phase_wall.items():
            self.phase_wall_total[k] = self.phase_wall_total.get(k, 0.0) + v
        return StepExecution(timeline=analytic, outputs=outputs,
                             backend=self.name, measured=report)

    # -- fused per-primitive preps ------------------------------------------
    # Each returns (launch, post): launch(bufs) -> (t_launch, out) issues
    # the group's device work asynchronously (t_launch taken AFTER any
    # cold compile+warm, so compile stays out of the samples); post(out,
    # parts) runs after the step barrier and only slices/merges/persists.

    def _prep_route(self, store, rec, q_of, reqs, mask_of, batch):
        holder = rec.holder
        ckv = self._committed_copy(store, rec.chunk_id, holder)
        mask = mask_of(rec.req_ids[0], rec.chunk_id)
        valid = (np.ones(ckv.shape[0], bool) if mask is None else mask)
        qs = [q_of(rid) for rid in rec.req_ids]
        homes = [reqs[rid].home for rid in rec.req_ids]
        for q, home in zip(qs, homes):
            check_route_shards(AXIS, q, ckv, valid, shard=home)
        cg = batch.add({holder: ckv}, tuple(ckv.shape), self.dtype)
        vg = batch.add({holder: valid}, (valid.shape[0],), jnp.bool_)
        PS = P(AXIS)
        PART = Partial(o=PS, m=PS, l=PS)

        if len(set(homes)) == 1:
            # one home: ship -> compute -> return in ONE program (the
            # probe ppermute existed only to time the wire floor; the
            # apportioning keeps its share of the fused wall)
            requester = homes[0]
            stacked = (jnp.concatenate(qs, axis=0) if len(qs) > 1
                       else qs[0])
            qg = batch.add({requester: stacked}, tuple(stacked.shape),
                           self.dtype)

            def launch(bufs):
                args = (bufs[qg], bufs[cg], bufs[vg])
                fn = self._fused_fn(
                    ("route-pair", holder, requester),
                    lambda: self._route_pair_program(holder, requester),
                    args)
                t_launch = time.perf_counter()
                return t_launch, fn(*args)

            def post(back, parts):
                merged = Partial(*(self._asm.take(x, requester)
                                   for x in back))
                off = 0
                for rid, q in zip(rec.req_ids, qs):
                    n = q.shape[0]
                    parts[rid].append(Partial(o=merged.o[off:off + n],
                                              m=merged.m[off:off + n],
                                              l=merged.l[off:off + n]))
                    off += n
            return launch, post

        # requesters span homes: gather -> compute -> exchange -> merge
        # fused into one program (same padded fanout schedule as serial)
        by_home: Dict[int, List[jax.Array]] = {}
        slices: Dict[int, Tuple[int, int, int]] = {}
        for rid, q, home in zip(rec.req_ids, qs, homes):
            blk = by_home.setdefault(home, [])
            start = sum(x.shape[0] for x in blk)
            blk.append(q)
            slices[rid] = (home, start, q.shape[0])
        b_pad = max(sum(x.shape[0] for x in blk)
                    for blk in by_home.values())
        blocks: Dict[int, jax.Array] = {}
        for home, blk in by_home.items():
            block = jnp.concatenate(blk, axis=0) if len(blk) > 1 else blk[0]
            if block.shape[0] < b_pad:
                pad = jnp.zeros(
                    (b_pad - block.shape[0],) + block.shape[1:],
                    block.dtype)
                block = jnp.concatenate([block, pad], axis=0)
            blocks[home] = block
        sample = next(iter(blocks.values()))
        qg = batch.add(blocks, (b_pad,) + tuple(sample.shape[1:]),
                       self.dtype)

        def launch(bufs):
            def build():
                def body(q, c, v):
                    g = fanout_gather(q, AXIS)
                    p = self._gated_partial(holder, g, c, v)
                    ex = fanout_exchange(p, AXIS)
                    return merge_stacked(ex.o, ex.m, ex.l)
                return self._shmap(body, (PS, PS, PS), PART)
            args = (bufs[qg], bufs[cg], bufs[vg])
            fn = self._fused_fn(("route-fan", holder), build, args)
            t_launch = time.perf_counter()
            return t_launch, fn(*args)

        def post(merged_g, parts):
            merged = {home: Partial(*(self._asm.take(x, home)
                                      for x in merged_g))
                      for home in blocks}
            for rid in rec.req_ids:
                home, start, n = slices[rid]
                mp = merged[home]
                parts[rid].append(Partial(o=mp.o[start:start + n],
                                          m=mp.m[start:start + n],
                                          l=mp.l[start:start + n]))
        return launch, post

    def _prep_fetch(self, store, rec, q_of, reqs, batch):
        src = fetch_source(rec)
        dst = rec.home if rec.home >= 0 else rec.holder
        ckv = self._committed_copy(store, rec.chunk_id, src)
        cg = batch.add({src: ckv}, tuple(ckv.shape), self.dtype)
        pg = batch.add({}, tuple(ckv.shape), self.dtype)
        qh = {rid: batch.put(q_of(rid), dst, key=("q", rid, dst))
              for rid in rec.req_ids}
        PS = P(AXIS)

        def launch(bufs):
            def build():
                def body(pool, c):
                    pulled = fetch_chunk(pool, c, None, 0, self.cfg,
                                         src, dst, AXIS)
                    # splice is elementwise over the last dim, so the
                    # per-shard application equals splicing the taken
                    # shard (what the serial path does)
                    return splice_delta_rotate(pulled, 0, self.cfg)
                return self._shmap(body, (PS, PS), PS)
            args = (bufs[pg], bufs[cg])
            fn = self._fused_fn(("fetch-fused", src, dst), build, args)
            t_launch = time.perf_counter()
            moved_g = fn(*args)
            moved_dev = self._asm.take(moved_g, dst)
            attends = []
            for rid in rec.req_ids:
                q = bufs[qh[rid]]
                afn = self._fused_fn(
                    ("attend", dst),
                    lambda: jax.jit(
                        lambda q, c: absorbed_partial(self.cfg, q, c)),
                    (q, moved_dev))
                p = afn(q, moved_dev)
                home = reqs[rid].home
                if home >= 0 and home != dst:
                    # the partial (not the cache) rides home so every
                    # partial of a request merges on ONE device
                    p = jax.device_put(p, self.devices[home])
                attends.append((rid, p))
            return t_launch, (moved_dev, attends)

        def post(out, parts):
            moved_dev, attends = out
            if rec.home >= 0 and store.resident_on(rec.chunk_id, rec.home):
                self._pool[(rec.chunk_id, rec.home)] = moved_dev
                store.set_replica_data(rec.chunk_id, rec.home,
                                       self._uncommit(moved_dev))
                keys = store.lookup(rec.chunk_id).index_keys
                if keys is not None:
                    store.set_replica_index_keys(rec.chunk_id, rec.home,
                                                 keys)
            for rid, p in attends:
                parts[rid].append(p)
        return launch, post

    def _prep_fetch_selected(self, store, rec, q_of, batch, sel):
        assert rec.primitive == "fetch", (
            f"selection fetch arrived as {rec.primitive!r}: replica spawns "
            "must never batch selected requests")
        rid = rec.req_ids[0]
        idx = np.nonzero(np.asarray(sel.masks[rec.chunk_id]))[0]
        if idx.size == 0:
            q = q_of(rid)
            ident = Partial.identity(q.shape[:-1], self.cfg.kv_lora_rank)
            return ((lambda bufs: (time.perf_counter(), ident)),
                    (lambda out, parts: parts[rid].append(out)))
        src = fetch_source(rec)
        dst = rec.home if rec.home >= 0 else rec.holder
        ckv = self._committed_copy(store, rec.chunk_id, src)
        cg = batch.add({src: ckv}, tuple(ckv.shape), self.dtype)
        pg = batch.add({}, (int(idx.size), ckv.shape[1]), self.dtype)
        qh = batch.put(q_of(rid), dst, key=("q", rid, dst))
        ix = jnp.asarray(idx)
        PS = P(AXIS)

        def launch(bufs):
            def build():
                def body(pool, c, ixa):
                    return fetch_scattered_gather(pool, c, ixa, 0,
                                                  self.cfg, src, dst, AXIS)
                return self._shmap(body, (PS, PS, P()), PS)
            args = (bufs[pg], bufs[cg], ix)
            fn = self._fused_fn(("fetch-gather-fused", src, dst), build,
                                args)
            t_launch = time.perf_counter()
            pulled = fn(*args)
            gathered = self._asm.take(pulled, dst)
            q = bufs[qh]
            afn = self._fused_fn(
                ("attend", dst),
                lambda: jax.jit(
                    lambda q, c: absorbed_partial(self.cfg, q, c)),
                (q, gathered))
            return t_launch, afn(q, gathered)

        def post(p, parts):
            parts[rid].append(p)
        return launch, post

    def _prep_local(self, store, rec, q_of, reqs, mask_of, batch):
        arr = self.ensure_chunk_data(store, rec.chunk_id)
        items = []
        for rid in rec.req_ids:
            inst = (reqs[rid].home if reqs[rid].home >= 0 else rec.holder)
            q_h = batch.put(q_of(rid), inst, key=("q", rid, inst))
            c_h = batch.put(arr, inst, key=("ckv", rec.chunk_id, inst))
            mask = mask_of(rid, rec.chunk_id)
            m_h = (None if mask is None else
                   batch.put(jnp.asarray(mask), inst,
                             key=("mask", rid, rec.chunk_id, inst)))
            items.append((rid, inst, q_h, c_h, m_h))

        def launch(bufs):
            calls = []
            for rid, inst, q_h, c_h, m_h in items:
                if m_h is None:
                    args = (bufs[q_h], bufs[c_h])
                    fn = self._fused_fn(
                        ("prefill", inst),
                        lambda: jax.jit(lambda q, c: absorbed_partial(
                            self.cfg, q, c)), args)
                else:
                    args = (bufs[q_h], bufs[c_h], bufs[m_h])
                    fn = self._fused_fn(
                        ("prefill-mask", inst),
                        lambda: jax.jit(lambda q, c, v: absorbed_partial(
                            self.cfg, q, c, v)), args)
                calls.append((rid, fn, args))
            t_launch = time.perf_counter()
            return t_launch, [(rid, fn(*args)) for rid, fn, args in calls]

        def post(outs, parts):
            for rid, p in outs:
                parts[rid].append(p)
        return launch, post
