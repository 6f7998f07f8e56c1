"""Beyond-paper: the three ROUTE schedules' measured collective footprints.

The paper measures one transport schedule (pairwise put + return). On TPU
the same primitive admits three shard_map schedules (core/routing.py):
pairwise ppermute, fan-out (all_gather q + all_to_all partials — the
scattered-selection shape), and ring (q+accumulator circulate; transfer
overlaps holder compute). This bench compiles all three on an 8-instance
mesh and reads their collective bytes + op counts off the HLO — the
schedule-selection data a TPU serving stack needs.
"""

import json
import os
import pathlib
import subprocess
import sys

from benchmarks.common import row

ROOT = pathlib.Path(__file__).resolve().parent.parent

_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.merge import Partial
from repro.core.routing import route_fanout, route_pairwise, route_ring
from repro.distributed.hlo_costs import analyse_hlo
from repro.models.mla import MLAConfig

CFG = MLAConfig()
NI, B, S_LOCAL = 8, 32, 2048
mesh = make_mesh((NI,), ("instance",))
q = jax.ShapeDtypeStruct((NI * B, CFG.n_heads, CFG.d_qk), jnp.bfloat16)
ckv = jax.ShapeDtypeStruct((NI * S_LOCAL, CFG.d_qk), jnp.bfloat16)
valid = jax.ShapeDtypeStruct((NI * S_LOCAL,), jnp.bool_)
out = {}

def compile_and_count(name, fn, specs, out_specs, args):
    sm = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=specs,
                               out_specs=out_specs))
    c = analyse_hlo(sm.lower(*args).compile().as_text(), NI)
    out[name] = {"wire": c.collective_wire_bytes,
                 "counts": {k: int(v) for k, v in
                            c.collective_counts.items()}}

pspec = Partial(o=P("instance"), m=P("instance"), l=P("instance"))
compile_and_count(
    "pairwise",
    lambda q, c: route_pairwise(CFG, q, c,
                                Partial.identity(q.shape[:-1],
                                                 CFG.kv_lora_rank),
                                holder=3, requester=0, axis="instance",
                                wire_dtype=jnp.bfloat16),
    (P("instance"), P("instance")), pspec, (q, ckv))
compile_and_count(
    "fanout",
    lambda q, c, v: route_fanout(CFG, q, c, v, axis="instance",
                                 wire_dtype=jnp.bfloat16),
    (P("instance"), P("instance"), P("instance")), pspec, (q, ckv, valid))
compile_and_count(
    "ring",
    lambda q, c, v: route_ring(CFG, q, c, v, axis="instance"),
    (P("instance"), P("instance"), P("instance")), pspec, (q, ckv, valid))
print("RESULT " + json.dumps(out))
"""


def run():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    # the child reads compiled collective bytes, not time: keep it on the
    # CPU so it never contends with a parent process that holds the chip
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _PROG], capture_output=True,
                       text=True, env=env, cwd=str(ROOT), timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    data = json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("RESULT ")][0][7:])
    rows = []
    for name, d in data.items():
        rows.append(row(f"schedules/{name}_wire_bytes", None,
                        "measured:compiled-HLO@8dev",
                        bytes=int(d["wire"]), counts=d["counts"]))
    # pairwise (1 holder) moves the least; fanout pays the all-holder
    # gather; ring multiplies by hops but buys transfer/compute overlap
    assert data["pairwise"]["wire"] < data["fanout"]["wire"]
    assert data["fanout"]["wire"] <= data["ring"]["wire"]
    rows.append(row("schedules/ring_over_fanout", None,
                    "measured:compiled-HLO@8dev",
                    ratio=round(data["ring"]["wire"]
                                / data["fanout"]["wire"], 2)))
    return rows
