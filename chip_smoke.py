"""Chip smoke test: the served decode step on a TPU, end to end.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # one 4-chip host (2x2): the mesh path

One chip: `repro.launch.serve`'s engine with the exec backend at
DeepSeek-V2 attention widths in bf16 (128 heads, d_qk 576, d_v 512): 4
logical instances in one pod, 32 corpus chunks of 2048 tokens (~75 MB of
latent cache), 12 agent sessions, 4 decode steps. Every step's outputs are
checked against the single-instance oracle. Then each Pallas kernel runs
once, compiled, against its pure-jnp reference at the same widths.

Four chips: the same trace through the shard_map backend, one instance per
chip, checked against the oracle, against an analytic run of the planner
(StepStats), and for where each instance's cache shard lives.

The run fails, and prints no result line, when JAX sees no TPU or fewer
chips than asked for. The last line of a passing run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# the served trace; --backend is added per phase
SERVE_ARGS = ["--mla", "deepseek-v2", "--dtype", "bfloat16",
              "--instances", "4", "--pods", "1", "--chunks", "32",
              "--chunk-tokens", "2048", "--agents", "12", "--steps", "4",
              "--selection-frac", "0"]

# Kernel check: max|kernel - ref| <= KERNEL_TOL * max(1, max|ref|) per
# output. Both sides read the same bf16 operands and accumulate in f32;
# they round the f32 softmax weights to bf16 for the MXU at different
# points (the reference after normalising, the kernel per cache block, if
# at all), up to 2^-9 relative per weight on each side. As for the served
# path (jax_exec.oracle_tolerance), that bounds an output's difference by
# 2^-8 times a weighted mean of |v| below 4: 2^-6.
KERNEL_TOL = 2.0 ** -6

# kernel shapes: DeepSeek-V2 widths, batch > 1
H, D_QK, D_V, ROPE = 128, 576, 512, 64
KERNEL_DIMS = dict(B=8, S=2048, KB=16, M=4, SQ=512, PREFILL_B=2)


class SmokeFailure(Exception):
    pass


class CompileClock:
    """Wall spent in XLA compilation (cache reads included), from JAX's
    own backend-compile events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def serve_args(backend: str, argv=SERVE_ARGS):
    from repro.launch import serve
    return serve.build_parser().parse_args(argv + ["--backend", backend])


def check_outputs(eng, reqs, step, tol) -> float:
    """Shapes, placement and the oracle check of one step; returns
    max|err|."""
    import jax
    from repro.serving.backends.jax_exec import max_oracle_err
    platform = jax.default_backend()
    cfg = eng.backend.cfg
    outs = eng.outputs_of(step)
    for rq in reqs:
        o = outs[rq.req_id].o
        if o.shape != (rq.m_q, cfg.n_heads, cfg.kv_lora_rank):
            raise SmokeFailure(f"step {step} request {rq.req_id}: output "
                               f"shape {o.shape}")
        where = {d.platform for d in o.devices()}
        if where != {platform}:
            raise SmokeFailure(f"step {step}: output on {where}")
    err = max_oracle_err(eng, reqs, step)
    if not err <= tol:                      # NaN fails too
        raise SmokeFailure(f"step {step}: max|err| {err:.3e} > tol "
                           f"{tol:.3e}")
    return err


def run_steps(eng, steps, tol, clock, on_step=None):
    import jax
    for reqs in steps:
        c0, t0 = clock.seconds, time.perf_counter()
        eng.schedule_step(reqs)
        st = eng.stats[-1]
        jax.block_until_ready(eng.outputs_of(st.step))
        wall = time.perf_counter() - t0
        compiling = clock.seconds - c0
        err = check_outputs(eng, reqs, st.step, tol)
        print(f"[chip_smoke] step {st.step}: {len(reqs)} requests "
              f"{st.primitives}, {st.n_resident}/{st.n_pairs} resident, "
              f"max|err| {err:.3e} (tol {tol:.3e}), wall {wall:.3f}s "
              f"after block_until_ready ({compiling:.3f}s compiling)")
        if on_step is not None:
            on_step(st)


def serve_phase(clock, argv=SERVE_ARGS):
    """One chip: serve's exec backend over the whole trace."""
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.serving.backends.jax_exec import oracle_tolerance
    # bf16 tolerance and why it fits the MXU: jax_exec.oracle_tolerance
    tol = oracle_tolerance(jnp.bfloat16)
    args = serve_args("exec", argv)
    eng = serve.build_engine(args)
    steps = serve.build_trace(args, eng)
    print(f"[chip_smoke] serve/exec: {args.mla} {args.dtype}, "
          f"{args.instances} instances, {args.chunks} x {args.chunk_tokens}"
          f"-token chunks, {args.agents} agents, {args.steps} steps")
    run_steps(eng, steps, tol, clock)


def shard_map_phase(clock, argv=SERVE_ARGS):
    """Four chips: the shard_map backend, one instance per chip, against
    the oracle and an analytic run of the same trace."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.serving.backends.jax_exec import oracle_tolerance
    tol = oracle_tolerance(jnp.bfloat16)
    args = serve_args("shard_map", argv)
    eng = serve.build_engine(args)
    steps = serve.build_trace(args, eng)
    ana_args = serve_args("analytic", argv)
    ana = serve.build_engine(ana_args)
    serve.build_trace(ana_args, ana, replay=steps)
    print(f"[chip_smoke] serve/shard_map: {args.mla} {args.dtype}, "
          f"{args.instances} instances on {args.instances} chips")

    def same_plan(st):
        ana.schedule_step(steps[st.step - 1])
        want = ana.stats[-1].comparable()
        if st.comparable() != want:
            raise SmokeFailure(f"step {st.step}: StepStats differ from "
                               f"the analytic run: {st.comparable()} vs "
                               f"{want}")

    run_steps(eng, steps, tol, clock, on_step=same_plan)
    backend = eng.backend
    devices = jax.devices()
    for i, dev in enumerate(backend.devices):
        if dev != devices[i]:
            raise SmokeFailure(f"instance {i} is bound to {dev}, not "
                               f"jax.devices()[{i}] = {devices[i]}")
    if not backend._pool:
        raise SmokeFailure("no instance shard was committed to a chip")
    for (chunk_id, inst), buf in backend._pool.items():
        if buf.devices() != {devices[inst]}:
            raise SmokeFailure(f"{chunk_id} of instance {inst} lives on "
                               f"{buf.devices()}, not {devices[inst]}")
    print(f"[chip_smoke] StepStats equal the analytic run on every step; "
          f"{len(backend._pool)} committed shards, each on its "
          f"instance's chip")


def kernel_phase(dims=KERNEL_DIMS, interpret=False):
    """Each Pallas kernel once, compiled, against its ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.delta_rotate import (delta_rotate_band,
                                            delta_rotate_ref)
    from repro.kernels.flash_prefill import flash_prefill, flash_prefill_ref
    from repro.kernels.mla_decode import mla_decode, mla_decode_ref
    from repro.kernels.softmax_merge import softmax_merge, softmax_merge_ref
    from repro.kernels.sparse_select import (sparse_select_decode,
                                             sparse_select_ref)
    from repro.serving.backends.jax_exec import DEEPSEEK_V2_MLA
    scale = DEEPSEEK_V2_MLA.scale
    B, S, KB, M, SQ, PB = (dims[k] for k in
                           ("B", "S", "KB", "M", "SQ", "PREFILL_B"))
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(keys[0], (B, H, D_QK), bf)
    ckv = jax.random.normal(keys[1], (B, S, D_QK), bf)

    def check(name, got, want):
        worst = (0.0, 0.0, 1.0)                 # (ratio, err, bound)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            if g.shape != w.shape:
                raise SmokeFailure(f"kernel {name}: shape {g.shape} vs "
                                   f"ref {w.shape}")
            err = float(np.max(np.abs(g - w)))
            bound = KERNEL_TOL * max(1.0, float(np.max(np.abs(w))))
            if not err <= bound:
                raise SmokeFailure(f"kernel {name}: max|err| {err:.3e} > "
                                   f"{bound:.3e}")
            worst = max(worst, (err / bound, err, bound))
        print(f"[chip_smoke] kernel {name}: matches ref.py, max|err| "
              f"{worst[1]:.3e} (bound {worst[2]:.3e})")

    got = mla_decode(q, ckv, d_v=D_V, scale=scale, interpret=interpret)
    check(f"mla_decode B={B} S={S}", got,
          mla_decode_ref(q, ckv, D_V, scale))

    rng = np.random.RandomState(0)
    idx = jnp.asarray(np.stack([np.sort(rng.choice(S // 64, KB,
                                                   replace=False))
                                for _ in range(B)]), jnp.int32)
    got = sparse_select_decode(q, ckv, idx, d_v=D_V, scale=scale,
                               interpret=interpret)
    check(f"sparse_select B={B} KB={KB}", got,
          sparse_select_ref(q, ckv, idx, D_V, 64, scale))

    # merge M partials of disjoint cache slices
    parts = [mla_decode_ref(q, ckv[:, i::M], D_V, scale) for i in range(M)]
    o, m, l = (jnp.stack([p[j] for p in parts]) for j in range(3))
    check(f"softmax_merge M={M} B={B}",
          softmax_merge(o, m, l, interpret=interpret),
          softmax_merge_ref(o, m, l))

    qp = jax.random.normal(keys[2], (PB, SQ, H, D_QK), bf)
    check(f"flash_prefill B={PB} Sq={SQ} Sk={S}",
          flash_prefill(qp, ckv[:PB], d_v=D_V, scale=scale,
                        interpret=interpret),
          flash_prefill_ref(qp, ckv[:PB], D_V, scale))

    band = ckv[0, :, D_QK - ROPE:]
    delta = jnp.float32(777)
    check(f"delta_rotate S={S}",
          delta_rotate_band(band, delta, head_dim=ROPE,
                            interpret=interpret),
          delta_rotate_ref(band, delta, ROPE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: exec backend + kernels on one chip; 4: the "
                         "shard_map backend over four chips, nothing else")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"[chip_smoke] FAIL: JAX's default platform is {platform!r}, "
              f"not 'tpu'; this check runs only on the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] FAIL: --chips {args.chips} needs "
              f"{args.chips} TPU devices, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch import serve
    cache = serve.enable_compile_cache()
    clock = CompileClock()
    kind = devices[0].device_kind
    print(f"[chip_smoke] device: {platform} {kind!r} x {len(devices)}")
    print(f"[chip_smoke] compile cache: {cache}")
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            serve_phase(clock)
            kernel_phase()
        else:
            shard_map_phase(clock)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(f"[chip_smoke] compile wall {clock.seconds:.3f}s over "
          f"{clock.count} compiles; total wall "
          f"{time.perf_counter() - t0:.3f}s (smoke reading, not a "
          f"benchmark)")
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
