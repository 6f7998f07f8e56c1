"""The benchmark's run: set up one cell, warm it, serve its traffic for a
fixed window, reduce what was recorded, and check the outputs against the
plain reference.

Everything specific to a cell is data found by name from BENCHMARK.json:
the configuration file it names, `<paths[0]>/traffic/<traffic>.json`,
and one reader per per-layer metric in `<paths[0]>/metrics/<name>.py`.

A configuration may also name a module of model hooks, `"model":
"<path under paths[0]>"` (for example `"models/dsv32-dsa.py"`), which
the harness loads by path as it loads the readers. The module may define
any of the hooks below; each one it leaves out is the harness's own.

* `make_backend(config)`: the execution backend (default `make_backend`).
* `build_engine(config, chunks, backend, seed)`: the engine over the
  corpus, `chunks` being the chunk arrays drawn from `seed` in corpus
  order, with whatever seeded state and selector the model needs
  (default `build_engine`). The rehearsal's throwaway engine and the
  served engine are both built by it.
* `program_keys(plan, placed)`: what a step's programs are keyed by,
  for the rehearsal (default `program_keys`).
* `step_flops(requests, config)`: the FLOPs the step required, counted
  from shapes; `requests` are `workload.Req` (default `step_flops`).
* `expected(config, seed, request, step, chunk_ids, chunk_arrays,
  dtype)`: the float32 reference output of one sampled request at the
  step that served it, `chunk_arrays` being its chunks' arrays in
  `chunk_ids` order, on one device (default `reference.expected`).
* `control(...)`, with `expected`'s arguments: the same output in the
  precision below the configuration's, which the check must refuse
  (default `reference.control`).

`expected` and `control` are the reference: they use nothing of the
program and nothing it made, only the configuration, the seed and the
chunk arrays the benchmark drew. A public name of the module outside
this list is an error at set-up, so a misspelt hook cannot fall back in
silence; helpers and constants are private (`_name`). Whatever the hooks
do, every sampled output is held to the configuration's
`check.max_abs_err` by one `Reading`, its shape against the reference's.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import heapq
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
import types
import zlib
from typing import Callable, Dict, List, Optional

import numpy as np

import flops as F
import reference as R
import tracereduce as TR
import workload as W

BENCH_DIR = pathlib.Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# outputs kept for the check: per stratum (each primitive that served a
# request, resident accesses, the largest m_q) and a uniform draw
SAMPLE_PER_KIND = 6
SAMPLE_UNIFORM = 24
# host spans the traced run records around calls into the program
SPANS = {"plan_step": "bench.plan_step", "submit": "bench.submit",
         "await_result": "bench.await", "_account": "bench.account"}


class SetupError(Exception):
    """The run cannot start here: no accelerator, too few chips, a device
    kind missing from the peak table, or a malformed cell."""


# -- the cell -----------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: W.Traffic
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: pathlib.Path


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: pathlib.Path, name: str) -> Cell:
    root = pathlib.Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json; cells: "
                         f"{sorted(cells)}")
    wl = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[wl["config"]]["file"]).read_text())
    bench_dir = root / spec["paths"][0]
    traffic = W.Traffic.from_dict(json.loads(
        (bench_dir / "traffic" / f"{wl['traffic']}.json").read_text()))
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _reports(m, name) and m["moves"] in moved]
    return Cell(name=name, chips=wl["chips"], config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer,
                bench_dir=bench_dir)


def load_module(path: pathlib.Path, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: pathlib.Path, name: str):
    """The `read(window)` function of bench_dir/metrics/<name>.py."""
    return load_module(bench_dir / "metrics" / f"{name}.py",
                       f"bench_metric_{name}").read


@dataclasses.dataclass(frozen=True)
class Model:
    """The hooks a configuration runs with (module docstring)."""
    make_backend: Callable
    build_engine: Callable
    program_keys: Callable
    step_flops: Callable
    expected: Callable
    control: Callable


HOOKS = tuple(f.name for f in dataclasses.fields(Model))


def load_model(bench_dir: pathlib.Path, config: dict) -> Model:
    """The configuration's hooks: those of the module its `"model"` key
    names, the harness's own for the rest."""
    hooks = {"make_backend": make_backend, "build_engine": build_engine,
             "program_keys": program_keys, "step_flops": step_flops,
             "expected": R.expected, "control": R.control}
    rel = config.get("model")
    if rel is None:
        return Model(**hooks)
    path = (bench_dir / rel).resolve()
    if not path.is_file() or bench_dir.resolve() not in path.parents:
        raise SetupError(f"model {rel!r} is no file under {bench_dir}")
    mod = load_module(path, f"bench_model_{path.stem.replace('-', '_')}")
    public = {n for n, v in vars(mod).items()
              if not n.startswith("_")
              and not isinstance(v, types.ModuleType)
              and getattr(v, "__module__", mod.__name__) == mod.__name__}
    unknown = sorted(public - set(HOOKS))
    if unknown:
        raise SetupError(f"model {rel!r} defines {unknown}, which are no "
                         f"hooks; hooks: {list(HOOKS)}")
    hooks.update({name: getattr(mod, name) for name in public})
    return Model(**hooks)


# -- what one window recorded --------------------------------------------------

@dataclasses.dataclass
class StepRecord:
    stats: object              # the engine's StepStats of the step
    plan_s: float              # engine.plan_walls entry
    exec_s: float              # host time in backend.submit + await_result
    flops: int                 # attention FLOPs the step required
    rows: int
    n_requests: int


@dataclasses.dataclass
class Window:
    """Input of every per-layer reader: the window's steps, its length,
    its compile events, the device's peaks and the reduced trace."""
    steps: List[StepRecord]
    seconds: float
    compiles: int
    chips: int
    peak: dict
    trace: Optional[TR.Summary] = None


class CompileClock:
    """JAX's backend-compile events, cache reads included."""

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


class GcClock:
    """Seconds the interpreter's cyclic garbage collector ran, by
    generation."""

    def __init__(self):
        self.seconds = [0.0, 0.0, 0.0]
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds[info["generation"]] += time.perf_counter() - self._t

    def close(self):
        gc.callbacks.remove(self._on)


class HostTimer:
    """Host seconds spent inside wrapped calls into the program, and, in
    a traced run, a profiler span around each."""

    def __init__(self, jax, annotate: bool):
        self.jax = jax
        self.annotate = annotate
        self.exec_s = 0.0

    def wrap(self, obj, method: str, timed: bool):
        orig = getattr(obj, method)
        label = SPANS[method]
        ann = self.jax.profiler.TraceAnnotation
        clock = time.perf_counter

        def call(*a, **k):
            t = clock()
            try:
                if self.annotate:
                    with ann(label):
                        return orig(*a, **k)
                return orig(*a, **k)
            finally:
                if timed:
                    self.exec_s += clock() - t

        setattr(obj, method, call)


class Sampler:
    """A seeded sample of the window's finished requests, with their
    outputs: for each stratum the requests of smallest seeded priority."""

    def __init__(self, seed: int):
        self.seed = seed
        self.heaps: Dict[str, list] = {}
        self.kept: Dict[tuple, dict] = {}

    def _priority(self, step: int, rid: int) -> int:
        return zlib.crc32(f"{self.seed}:{step}:{rid}".encode())

    def offer(self, step: int, req, kinds, output) -> None:
        key = (step, req.req_id)
        pri = self._priority(step, req.req_id)
        entry = {"step": step, "req": req, "o": output, "kinds": kinds,
                 "refs": 0}
        for stratum in kinds | {"uniform"}:
            cap = SAMPLE_UNIFORM if stratum == "uniform" else SAMPLE_PER_KIND
            heap = self.heaps.setdefault(stratum, [])
            item = (-pri, key)
            if len(heap) < cap:
                heapq.heappush(heap, item)
            elif item > heap[0]:
                _, out = heapq.heapreplace(heap, item)
                self._release(out)
            else:
                continue
            self.kept.setdefault(key, entry)["refs"] += 1

    def _release(self, key) -> None:
        e = self.kept[key]
        e["refs"] -= 1
        if e["refs"] == 0:
            del self.kept[key]

    def entries(self) -> List[dict]:
        return [self.kept[k] for k in sorted(self.kept)]


# -- set-up -------------------------------------------------------------------

def devices_for(jax, chips: int, require_tpu: bool):
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise SetupError(f"JAX's platform is {platform!r}, not 'tpu': the "
                         f"benchmark measures only on the chip")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def peak_of(kind: str, table: Optional[dict] = None) -> dict:
    table = table if table is not None else json.loads(
        (BENCH_DIR / "peaks.json").read_text())
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


CHUNK_GROUP = 64


def make_chunks(jax, seed: int, n: int, tokens: int, width: int, dtype,
                devices=(None,)):
    """Every chunk's latent array, made on the device from the seed by one
    jitted program: n arrays of (tokens, width) standard normals in the
    served dtype, chunk i from the seed's key folded with i. Chunk i is
    drawn on devices[i % len(devices)]: with one instance per chip, the
    chip of the instance that holds it (workload.holder_of); None is
    JAX's default device, uncommitted. The program draws CHUNK_GROUP
    chunks at a time, so the draw's transient stays a group's size and
    not the corpus's."""
    import jax.numpy as jnp
    k = len(devices)
    per = -(-n // k)
    group = min(per, CHUNK_GROUP)

    @jax.jit
    def gen(key, idx):
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
        x = jax.vmap(lambda k: jax.random.normal(k, (tokens, width),
                                                 dtype))(keys)
        return tuple(x[i] for i in range(group))

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)
    out = [None] * n
    for h, dev in enumerate(devices):
        for first in range(0, per, group):
            idx = (h + k * (first + np.arange(group))).astype(np.int32)
            args = (key, jnp.asarray(idx))
            if dev is not None:
                args = jax.device_put(args, dev)
            for i, x in zip(idx.tolist(), gen(*args)):
                if i < n:
                    out[i] = x
    return out


def make_backend(config: dict):
    """The execution backend the configuration names, at its widths."""
    import jax.numpy as jnp
    from repro.models.mla import MLAConfig
    srv = config["serving"]
    mla = MLAConfig(d_model=config["hidden_size"],
                    n_heads=config["num_attention_heads"],
                    kv_lora_rank=config["kv_lora_rank"],
                    q_lora_rank=config["q_lora_rank"],
                    qk_nope_head_dim=config["qk_nope_head_dim"],
                    qk_rope_head_dim=config["qk_rope_head_dim"],
                    v_head_dim=config["v_head_dim"],
                    rope_theta=float(config["rope_theta"]))
    if srv["backend"] == "exec":
        from repro.serving.backends.jax_exec import JaxExecBackend as B
    elif srv["backend"] == "shard_map":
        from repro.serving.backends.shard_map import ShardMapExecBackend as B
    else:
        raise SetupError(f"unknown backend {srv['backend']!r}")
    return B(cfg=mla, dtype=jnp.dtype(srv["dtype"]))


def build_engine(config: dict, chunks: list, backend, seed: int):
    """The engine, straight from the configuration file, with the corpus
    registered and every chunk's array attached. `seed` is not needed
    here: the chunks already carry its data."""
    from repro.serving.engine import EngineConfig, ServingEngine
    srv, corpus = config["serving"], config["corpus"]
    n_inst = srv["instances"]
    eng = ServingEngine(n_inst, pool_tokens=srv["pool_tokens"],
                        cfg=EngineConfig(intra_pod_fabric=srv["fabric"]),
                        instances_per_pod=max(1, n_inst // srv["pods"]),
                        backend=backend)
    for i, cid in enumerate(W.chunk_ids(corpus["chunks"])):
        eng.register_chunk(cid, W.holder_of(i, n_inst),
                           corpus["chunk_tokens"])
        eng.store.attach_data(cid, chunks[i])
    return eng


def program_keys(plan, placed: bool) -> set:
    """What a step's programs are keyed by, as far as the plan shows it:
    each dispatch's primitive and its members' m_q in order, each resident
    access's m_q, each request's m_q and partial count for the merge; on
    several chips also where each of them runs."""
    m_q = {r.req_id: r.m_q for r in plan.requests}
    home = {r.req_id: r.home for r in plan.requests}
    at = (lambda x: x) if placed else (lambda x: 0)
    keys = set()
    for rec in plan.records:
        if not rec.backup:
            keys.add((rec.primitive, at(rec.holder), at(rec.home),
                      at(rec.link_instance),
                      tuple((at(home[i]), m_q[i]) for i in rec.req_ids)))
    for rp in plan.resident_pairs:
        keys.add(("resident", at(rp.instance), m_q[rp.req_id]))
    for r in plan.requests:
        keys.add(("merge", at(r.home), r.m_q, len(r.chunk_ids)))
    return keys


def step_flops(requests, config: dict) -> int:
    """The dense attention FLOPs of a step (bench/flops.py) at the
    configuration's geometry."""
    geom = R.Geometry.of(config)
    return F.step_flops(requests, config["corpus"]["chunk_tokens"],
                        geom.n_heads, geom.d_qk, geom.d_v)


# steps a second the rehearsal provides for: about 1.5 times the rate at
# which the served step runs on a v5e (PERF.md, section 6)
REHEARSE_STEPS_PER_S = 5.0


def rehearse_horizon(traffic: W.Traffic, seconds: float) -> int:
    """How many steps of the trace set-up prepares: the warm-up and as many
    as a window of `seconds` serves at REHEARSE_STEPS_PER_S."""
    return traffic.warmup_steps + math.ceil(seconds * REHEARSE_STEPS_PER_S)


def rehearse(jax, model: Model, config: dict, chunks: list, backend,
             traffic: W.Traffic, seed: int, steps: int) -> int:
    """Compile, in set-up, the programs the run's first `steps` steps will
    need. A throwaway engine plans each of those steps (plans are
    deterministic, so they equal the served engine's) and executes, on the
    backend the served engine will use, every step whose plan shows a
    program key not seen before. Returns the steps executed."""
    eng = model.build_engine(config, chunks, backend, seed)
    gen = W.sessions(traffic, config["corpus"]["chunks"],
                     config["serving"]["instances"], seed)
    placed = config["serving"]["chips"] > 1
    seen: set = set()
    executed = 0
    for _ in range(steps):
        plan = eng.plan_step([to_request(r) for r in next(gen)])
        keys = model.program_keys(plan, placed)
        if keys <= seen:
            continue
        seen |= keys
        ticket = backend.submit(eng, plan)
        done = (ticket.execution if ticket.execution is not None
                else backend.await_result(eng, ticket))
        jax.block_until_ready(done.outputs)
        executed += 1
    return executed


def to_request(r: W.Req):
    from repro.serving.engine import Request
    return Request(req_id=r.req_id, home=r.home, chunk_ids=list(r.chunk_ids),
                   m_q=r.m_q, expected_reuse_steps=r.expected_reuse_steps,
                   query_seed=r.query_seed)


def kinds_of(plan) -> Dict[int, set]:
    """Which paths served each request of a step: the primitive of every
    dispatch it rode, and "resident" for local accesses."""
    kinds: Dict[int, set] = {}
    for rec in plan.records:
        if not rec.backup:
            for rid in rec.req_ids:
                kinds.setdefault(rid, set()).add(rec.primitive)
    for rp in plan.resident_pairs:
        kinds.setdefault(rp.req_id, set()).add("resident")
    return kinds


# -- the loop -----------------------------------------------------------------

class ServeLoop:
    """Drives the engine as `serve.main` does: schedule_step, speculate the
    next step at depth >= 2, flush at the end; then waits for each
    accounted step's outputs. A request is due when its step is handed to
    schedule_step and done when its output is ready."""

    def __init__(self, jax, eng, gen, flops: Callable[[list], int],
                 timer: Optional[HostTimer]):
        self.jax = jax
        self.eng = eng
        self.gen = gen
        self.flops = flops
        self.timer = timer
        self.depth = max(1, eng.cfg.pipeline_depth)
        self.ahead = next(gen)
        self.pending: List[tuple] = []        # (t_due, requests) in order
        self.accounted = 0
        self.records: List[StepRecord] = []
        self.latencies: List[float] = []
        self.rows = 0
        self.done = 0
        self.sampler: Optional[Sampler] = None
        self.walls: List[float] = []           # each step() call's wall
        self._exec_mark = 0.0
        self.span = (jax.profiler.TraceAnnotation if timer is not None
                     and timer.annotate else None)

    def step(self) -> None:
        reqs, self.ahead = self.ahead, next(self.gen)
        t = time.perf_counter()
        self.pending.append((t, reqs))
        self.eng.schedule_step([to_request(r) for r in reqs])
        if self.depth >= 2:
            self.eng.speculate_step([to_request(r) for r in self.ahead])
        if self.span is None:
            self.collect()
        else:
            with self.span("bench.collect"):
                self.collect()
        self.walls.append(time.perf_counter() - t)

    def slowest(self, n: int) -> List[tuple]:
        """The n longest calls of step() since the window opened, as
        (index in the window, milliseconds)."""
        top = sorted(enumerate(self.walls), key=lambda w: -w[1])[:n]
        return [(i, round(w * 1e3, 1)) for i, w in top]

    def finish(self) -> None:
        self.eng.flush()
        self.collect()

    def collect(self) -> None:
        eng = self.eng
        while self.accounted < len(eng.stats):
            i = self.accounted
            self.accounted += 1
            t_due, reqs = self.pending.pop(0)
            plan, stats = eng.plans[i], eng.stats[i]
            outs = eng.outputs_of(stats.step)
            done = {}
            for r in reqs:
                out = outs.get(r.req_id)
                if out is not None:
                    self.jax.block_until_ready(out)
                    done[r.req_id] = time.perf_counter()
            for r in reqs:
                if r.req_id in done:
                    self.latencies.append(done[r.req_id] - t_due)
                    self.rows += r.m_q
                    self.done += 1
            exec_s = 0.0
            if self.timer is not None:
                exec_s = self.timer.exec_s - self._exec_mark
                self._exec_mark = self.timer.exec_s
            self.records.append(StepRecord(
                stats=stats, plan_s=eng.plan_walls[i], exec_s=exec_s,
                flops=self.flops(reqs), rows=sum(r.m_q for r in reqs),
                n_requests=len(reqs)))
            if self.sampler is not None:
                kinds = kinds_of(plan)
                top = max(r.m_q for r in reqs)
                for r in reqs:
                    if r.req_id in done:
                        k = set(kinds.get(r.req_id, ()))
                        if r.m_q == top:
                            k.add("largest_m_q")
                        self.sampler.offer(stats.step, r, k,
                                           outs[r.req_id].o)

    def start_window(self, seed: int) -> None:
        """Forget the warm-up: from here on every request is the window's."""
        self.records.clear()
        self.latencies.clear()
        self.walls.clear()
        self.rows = self.done = 0
        self.sampler = Sampler(seed)


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by linear interpolation between order
    statistics (numpy's default), over every value."""
    return float(np.percentile(np.asarray(values, np.float64), q))


# -- the check ----------------------------------------------------------------

@dataclasses.dataclass
class Reading:
    """The one predicate of the check: outputs held against the reference
    under the configuration's limit, with their widest gap."""
    limit: float
    worst: float = 0.0
    over: int = 0
    n: int = 0

    def add(self, g: float) -> None:
        self.n += 1
        if not g <= self.limit:                  # NaN fails too
            self.over += 1
        self.worst = max(self.worst, g) if g == g else float("inf")

    @property
    def passes(self) -> bool:
        return self.over == 0 and self.n > 0


def check(entries: List[dict], chunks: list, model: Model, config: dict,
          seed: int, dtype, limit: float, device, control: bool = False):
    """Each sampled request's served output against the model's reference
    (`expected`). With `control`, the model's control is put in the
    program's place on the same requests and read by the same predicate.
    An output of another shape than the reference's reads an infinite
    gap. The reference gets a request's chunk arrays on `device`, one
    device, wherever the program holds them. Returns the program's
    Reading and the control's (or None)."""
    import jax
    index = {cid: i for i, cid in enumerate(W.chunk_ids(len(chunks)))}
    served = Reading(limit)
    ctrl = Reading(limit) if control else None
    for e in entries:
        r = e["req"]
        arrays = [jax.device_put(chunks[index[c]], device)
                  for c in r.chunk_ids]
        args = (config, seed, r, e["step"], r.chunk_ids, arrays, dtype)
        want = model.expected(*args)
        o = e["o"]
        served.add(R.gap(o, want) if tuple(o.shape) == tuple(want.shape)
                   else float("inf"))
        if ctrl is not None:
            ctrl.add(R.gap(model.control(*args), want))
    return served, ctrl


# -- one run ------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """A finished run: the result line and the lines that close stderr."""
    result: dict
    check_lines: List[str]
    # the control's verdict by the program's predicate, where it was run
    control_correct: Optional[bool] = None
    # what the per-layer readers read
    window: Optional[Window] = None


def run(root, workload: str, seed: int, seconds: float, trace: bool,
        *, require_tpu: bool = True, peaks: Optional[dict] = None,
        t_start: Optional[float] = None, control: bool = False,
        log=None) -> Run:
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(f"[bench] {msg}", file=sys.stderr,
                                    flush=True))
    cell = load_cell(root, workload)
    model = load_model(cell.bench_dir, cell.config)
    import jax
    import jax.numpy as jnp
    devices = devices_for(jax, cell.chips, require_tpu)
    kind = devices[0].device_kind
    peak = peak_of(kind, peaks)
    from repro.launch import serve
    cache = serve.enable_compile_cache()
    clock = CompileClock(jax)
    cfg, srv, corpus = cell.config, cell.config["serving"], \
        cell.config["corpus"]
    geom = R.Geometry.of(cfg)
    dtype = jnp.dtype(srv["dtype"])
    log(f"{workload}: {kind} x {len(devices)}, seed {seed}, compile cache "
        f"{cache}")

    # on several chips each chunk is drawn on its holder's chip, where the
    # shard_map backend serves it from
    on = devices[:srv["instances"]] if cell.chips > 1 else (None,)
    chunks = make_chunks(jax, seed, corpus["chunks"], corpus["chunk_tokens"],
                         geom.d_qk, dtype, on)
    backend = model.make_backend(cfg)
    horizon = rehearse_horizon(cell.traffic, seconds)
    rehearsed = rehearse(jax, model, cfg, chunks, backend, cell.traffic,
                         seed, horizon)
    eng = model.build_engine(cfg, chunks, backend, seed)
    timer = None
    if trace:
        timer = HostTimer(jax, annotate=True)
        timer.wrap(eng, "plan_step", timed=False)
        timer.wrap(eng, "_account", timed=False)
        timer.wrap(eng.backend, "submit", timed=True)
        timer.wrap(eng.backend, "await_result", timed=True)
    gen = W.sessions(cell.traffic, corpus["chunks"], srv["instances"], seed)
    drv = ServeLoop(jax, eng, gen, lambda reqs: model.step_flops(reqs, cfg),
                    timer)
    for _ in range(cell.traffic.warmup_steps):
        drv.step()
    drv.finish()

    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
    drv.start_window(seed)
    # the rehearsal engine and the warm-up leave garbage behind: collect it
    # now, and keep set-up's survivors out of the window's collections, as
    # a server does once it is warm
    gc.collect()
    gc.freeze()
    gc_clock = GcClock()
    c0, cs0 = clock.count, clock.seconds
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    step_span = jax.profiler.TraceAnnotation
    while time.perf_counter() - t0 < seconds:
        if trace:
            with step_span(TR.STEP_SPAN):
                drv.step()
        else:
            drv.step()
    drv.finish()
    window_s = time.perf_counter() - t0
    compiles, compile_s = clock.count - c0, clock.seconds - cs0
    gc_clock.close()
    gc.unfreeze()
    summary = None
    if trace:
        jax.profiler.stop_trace()
        found = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        if found:
            summary = TR.reduce(TR.extract(found[0]))
        shutil.rmtree(tmp, ignore_errors=True)

    peaks_used = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices[:cell.chips]]
    memory_peak = max(peaks_used)
    attempted = sum(r.n_requests for r in drv.records)
    unfinished = attempted - drv.done
    entries = drv.sampler.entries()
    # free the program's state before the reference runs
    del eng, drv.eng, timer, backend
    gc.collect()
    limit = cfg["check"]["max_abs_err"]
    served, ctrl = check(entries, chunks, model, cfg, seed, dtype, limit,
                         devices[0], control=control)

    window = Window(steps=drv.records, seconds=window_s, compiles=compiles,
                    chips=cell.chips, peak=peak, trace=summary)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = metric_reader(cell.bench_dir, m["name"])(window)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"rows_per_s": drv.rows / window_s,
               "latency_ms_p95": (percentile(drv.latencies, 95) * 1e3
                                  if drv.latencies else None),
               "setup_s": setup_s}
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    checks = {"max_abs_err": {"value": served.worst, "limit": limit},
              "sampled_over_limit": {"value": served.over, "limit": 0},
              "unfinished": {"value": unfinished, "limit": 0}}
    finished = unfinished == 0 and attempted > 0
    correct = served.passes and finished
    if ctrl is not None:
        checks["control_max_abs_err"] = {"value": ctrl.worst,
                                         "limit": limit}
        checks["control_over_limit"] = {"value": ctrl.over, "limit": 0}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak),
              "memory_peak_bytes_by_chip": [int(b) for b in peaks_used]}
    result = {"correct": correct, "attempted": attempted,
              "failed": unfinished + served.over,
              "metrics": metrics, "device": device}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    lat = sorted(drv.latencies) or [0.0]
    log(f"window {window_s:.3f} s: {len(window.steps)} steps, {attempted} "
        f"requests, {drv.rows} rows, {compiles} compile events "
        f"({compile_s:.3f} s); garbage collection "
        f"{' '.join(f'{x:.3f}' for x in gc_clock.seconds)} s by "
        f"generation; set-up {setup_s:.3f} s with {rehearsed} of "
        f"{horizon} steps rehearsed, {c0} compile events ({cs0:.3f} s); "
        f"latency ms p50 {percentile(lat, 50) * 1e3:.1f} "
        f"p95 {percentile(lat, 95) * 1e3:.1f} max {lat[-1] * 1e3:.1f}; "
        f"slowest steps (index, ms) {drv.slowest(5)}; "
        f"memory peak by chip "
        f"{' '.join(f'{b / 1e9:.3f}' for b in peaks_used)} GB; sampled "
        f"{served.n} requests")
    result["checks"] = checks
    lines = [f"check {k}: {v['value']} (limit {v['limit']})"
             for k, v in checks.items()]
    return Run(result=result, check_lines=lines,
               control_correct=(None if ctrl is None
                                else ctrl.passes and finished),
               window=window)
