"""§2.1/§5.2 MEASURED on our own system: compile the ROUTE and FETCH
shard_map programs on an 8-instance mesh and read the actual collective
bytes off the compiled HLO — the byte asymmetry as the compiler sees it.

Runs in a subprocess (needs 8 host devices; benches keep 1)."""

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
from repro.core.routing import route_fanout, route_pairwise
from repro.core.splice import fetch_chunk
from repro.distributed.hlo_costs import analyse_hlo
from repro.models.mla import MLAConfig

CFG = MLAConfig()                      # real V2 geometry: d_qk=576, d_v=512
NI, B, S_LOCAL, CT = 8, 32, 2048, 2048
mesh = make_mesh((NI,), ("instance",))

def route_prog(q, ckv, valid):
    return route_pairwise(CFG, q, ckv,
                          Partial.identity(q.shape[:-1], CFG.kv_lora_rank),
                          holder=3, requester=0, axis="instance",
                          wire_dtype=jnp.bfloat16)   # paper 1032-B partial

def fetch_prog(pool, ckv):
    return fetch_chunk(pool, ckv[:CT], delta=128, dst_offset=0, cfg=CFG,
                       holder=3, requester=0, axis="instance")

out = {}
q = jax.ShapeDtypeStruct((NI * B, CFG.n_heads, CFG.d_qk), jnp.bfloat16)
ckv = jax.ShapeDtypeStruct((NI * S_LOCAL, CFG.d_qk), jnp.bfloat16)
valid = jax.ShapeDtypeStruct((NI * S_LOCAL,), jnp.bool_)
pool = jax.ShapeDtypeStruct((NI * S_LOCAL, CFG.d_qk), jnp.bfloat16)

sm = jax.jit(jax.shard_map(route_prog, mesh=mesh,
                           in_specs=(P("instance"), P("instance"),
                                     P("instance")),
                           out_specs=Partial(o=P("instance"),
                                             m=P("instance"),
                                             l=P("instance"))))
txt = sm.lower(q, ckv, valid).compile().as_text()
c = analyse_hlo(txt, NI)
out["route"] = {"wire": c.collective_wire_bytes,
                "result": c.collective_result_bytes}

sm2 = jax.jit(jax.shard_map(fetch_prog, mesh=mesh,
                            in_specs=(P("instance"), P("instance")),
                            out_specs=P("instance")))
txt2 = sm2.lower(pool, ckv).compile().as_text()
c2 = analyse_hlo(txt2, NI)
out["fetch"] = {"wire": c2.collective_wire_bytes,
                "result": c2.collective_result_bytes}
out["q_rows"] = B
out["ct"] = CT
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
                       text=True, env=env, cwd=str(ROOT), timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    data = json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("RESULT ")][0][7:])
    rows = []
    # XLA:CPU float-normalizes bf16 collectives to f32 (verified on a bare
    # bf16 ppermute), inflating BOTH sides 2x vs the TPU target where bf16
    # collectives are native — the ratio is unaffected; the tpu_native
    # columns divide the payload terms back.
    route_b = data["route"]["wire"]
    fetch_b = data["fetch"]["wire"]
    rows.append(row("hlo/route_wire_bytes", None,
                    "measured:compiled-HLO@8dev(cpu-f32-normalized)",
                    bytes=int(route_b), tpu_native_bytes=int(route_b // 2),
                    q_rows=data["q_rows"]))
    rows.append(row("hlo/fetch_wire_bytes", None,
                    "measured:compiled-HLO@8dev(cpu-f32-normalized)",
                    bytes=int(fetch_b), tpu_native_bytes=int(fetch_b // 2),
                    chunk_tokens=data["ct"]))
    rows.append(row("hlo/fetch_over_route", None,
                    "measured:compiled-HLO@8dev",
                    ratio=round(fetch_b / route_b, 1)))
    # model-vs-measured agreement at this exact shape: 512 absorbed rows x
    # (q+p) vs c_t x b_KV (one layer)
    from repro.core import cost_model as cm
    model_route = cm.route_wire_bytes(data["q_rows"] * 16)
    model_fetch = cm.fetch_wire_bytes(data["ct"])
    rows.append(row("hlo/model_agreement", None, "model-vs-measured",
                    model_ratio=round(model_fetch / model_route, 2),
                    measured_ratio=round(fetch_b / route_b, 2)))
    # the measured asymmetry: fetching the 2k chunk moves far more bytes
    # than routing the decode queries (paper: >=76% fewer at M_q<=256;
    # our per-instance M_q = 32 rows x 16 heads = 512 absorbed rows)
    assert fetch_b > 2 * route_b, (fetch_b, route_b)
    return rows
