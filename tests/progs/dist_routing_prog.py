"""Distributed routing correctness on a real 8-device mesh (subprocess-only:
forces 8 host devices, so it must NOT run inside the main pytest process).

Verifies §3.3 on the production shard_map transport: fanout, ring, pairwise
routing all reproduce single-instance attention over the concatenated cache;
TPLA rank-pairing (§8) halves/quarters per-rank inter-instance bytes.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.merge import Partial
from repro.core.routing import (route_fanout, route_pairwise,
                                route_pairwise_tpla, route_ring)
from repro.distributed.hlo_analysis import parse_collectives
from repro.launch.mesh import make_mesh
from repro.models import mla as M
from repro.models.module import KeyGen, split

CFG = M.MLAConfig(d_model=256, n_heads=4, kv_lora_rank=64,
                  qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
NI = 8           # instances
B, S_LOCAL = 2, 64
S = NI * S_LOCAL


def build_inputs(seed=0):
    kg = KeyGen(jax.random.PRNGKey(seed))
    params, _ = split(M.init_mla(kg, CFG, dtype=jnp.float32))
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                (1, S, CFG.d_model), jnp.float32)
    pos = jnp.arange(S)[None]
    ckv = M.latent_cache_entries(params, CFG, x, pos)[0]          # (S, 576')
    # per-instance decode queries: NI*B rows total
    xq = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                 (1, NI * B, CFG.d_model), jnp.float32)
    qn, qr = M.project_q(params, CFG, xq,
                         jnp.full((1, NI * B), S, jnp.float32))
    q_abs = M.absorb_query(params, CFG, qn, qr)[0]                # (NI*B, H, d)
    return q_abs, ckv


def test_fanout_and_ring():
    mesh = make_mesh((NI,), ("instance",))
    q_abs, ckv = build_inputs()
    valid = jnp.ones(S, bool)

    def fan(q, c, v):
        return route_fanout(CFG, q, c, v, axis="instance")

    def ring(q, c, v):
        return route_ring(CFG, q, c, v, axis="instance")

    specs = (P("instance"), P("instance"), P("instance"))
    out_specs = Partial(o=P("instance"), m=P("instance"), l=P("instance"))
    for name, fn in (("fanout", fan), ("ring", ring)):
        shmapped = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                         out_specs=out_specs))
        got = shmapped(q_abs, ckv, valid)
        want = M.absorbed_partial(CFG, q_abs, ckv)
        err = np.max(np.abs(np.asarray(got.o) - np.asarray(want.o)))
        assert err <= 5e-6, (name, err)
        np.testing.assert_allclose(np.asarray(got.l), np.asarray(want.l),
                                   rtol=1e-5)
        print(f"  {name}: max|err| = {err:.2e}")

    # scattered residency (§5.4): random disjoint valid masks, same exactness
    rng = np.random.RandomState(0)
    owner = rng.randint(0, NI, S)
    valid_scattered = jnp.asarray(
        (owner == (np.arange(S) // S_LOCAL)))   # each owns subset of own range
    shmapped = jax.jit(jax.shard_map(fan, mesh=mesh, in_specs=specs,
                                     out_specs=out_specs))
    got = shmapped(q_abs, ckv, valid_scattered)
    want = M.absorbed_partial(CFG, q_abs, ckv,
                              jnp.asarray(np.asarray(valid_scattered))[None, None, :])
    err = np.max(np.abs(np.asarray(got.o) - np.asarray(want.o)))
    assert err <= 5e-6, err
    print(f"  fanout scattered: max|err| = {err:.2e}")


def test_pairwise():
    mesh = make_mesh((NI,), ("instance",))
    q_abs, ckv = build_inputs(seed=7)
    requester, holder = 0, 3

    def pw(q, c):
        # requester's local partial over its own resident shard
        local = M.absorbed_partial(CFG, q, c)
        return route_pairwise(CFG, q, c, local, holder=holder,
                              requester=requester, axis="instance")

    out_specs = Partial(o=P("instance"), m=P("instance"), l=P("instance"))
    shmapped = jax.jit(jax.shard_map(pw, mesh=mesh,
                                     in_specs=(P("instance"), P("instance")),
                                     out_specs=out_specs))
    got = shmapped(q_abs, ckv)
    # requester's rows: merged over shard(requester) + shard(holder)
    mine = slice(requester * B, (requester + 1) * B)
    own = ckv[requester * S_LOCAL:(requester + 1) * S_LOCAL]
    his = ckv[holder * S_LOCAL:(holder + 1) * S_LOCAL]
    want = M.absorbed_partial(CFG, q_abs[mine],
                              jnp.concatenate([own, his], axis=0))
    err = np.max(np.abs(np.asarray(got.o)[mine] - np.asarray(want.o)))
    assert err <= 5e-6, err
    print(f"  pairwise: max|err| = {err:.2e}")


def test_tpla_rank_pairing():
    NTP = 4
    mesh = make_mesh((2, NTP), ("instance", "tp"))
    q_abs, ckv = build_inputs(seed=11)
    q_abs = q_abs[: 2 * B]
    holder_cache = ckv[:S_LOCAL]
    d_c, d_r = CFG.kv_lora_rank, CFG.qk_rope_head_dim

    # column-partition: rank r gets [latent_r | rope_r]
    def rank_slice(arr):
        lat = arr[..., :d_c].reshape(*arr.shape[:-1], NTP, d_c // NTP)
        rope = arr[..., d_c:].reshape(*arr.shape[:-1], NTP, d_r // NTP)
        out = jnp.concatenate([lat, rope], axis=-1)       # (..., NTP, cols)
        return jnp.moveaxis(out, -2, 0)                   # (NTP, ..., cols)

    q_sl = rank_slice(q_abs)                  # (NTP, 2B, H, 144)
    c_sl = rank_slice(holder_cache)           # (NTP, S_LOCAL, 144)
    # broadcast the holder's cache slices to both instances (holder=1 uses it)
    c_both = jnp.broadcast_to(c_sl[None], (2,) + c_sl.shape)   # (2, NTP, S, 144)
    q_both = q_sl.reshape(NTP, 2, B, CFG.n_heads, -1).transpose(1, 0, 2, 3, 4)

    def tpla(q, c):
        q, c = q[0, 0], c[0, 0]               # strip mapped dims
        part = route_pairwise_tpla(CFG, q, c, holder=1, requester=0,
                                   instance_axis="instance", tp_axis="tp")
        return part.o[None, None], part.m[None, None], part.l[None, None]

    fn = jax.jit(jax.shard_map(
        tpla, mesh=mesh,
        in_specs=(P("instance", "tp"), P("instance", "tp")),
        out_specs=(P("instance", "tp", None, None, None),
                   P("instance", "tp", None, None),
                   P("instance", "tp", None, None))))
    o, m, l = fn(q_both, c_both)
    # requester = instance 0: concat rank slices of o -> (B, H, d_c)
    o_req = np.concatenate([np.asarray(o[0, r]) for r in range(NTP)], axis=-1)
    want = M.absorbed_partial(CFG, q_abs[:B], holder_cache)
    err = np.max(np.abs(o_req[:B].reshape(B, CFG.n_heads, d_c)
                        - np.asarray(want.o[:B])))
    assert err <= 5e-6, err
    print(f"  tpla rank-paired: max|err| = {err:.2e}")

    # §8: per-rank inter-instance bytes fall by 1/N. Count collective-permute
    # bytes in the compiled HLO and compare against the unsliced pairwise.
    hlo_tpla = fn.lower(q_both, c_both).compile().as_text()
    cp_tpla = parse_collectives(hlo_tpla).result_bytes.get(
        "collective-permute", 0)

    mesh1 = make_mesh((2, NTP), ("instance", "tp"))
    def plain(q, c):
        q, c = q[0, 0], c[0, 0]
        part = route_pairwise(CFG, q, c,
                              Partial.identity(q.shape[:-1], d_c),
                              holder=1, requester=0, axis="instance")
        return part.o[None, None], part.m[None, None], part.l[None, None]
    q_rep = jnp.broadcast_to(q_abs[:B][None, None],
                             (2, NTP) + q_abs[:B].shape)
    c_rep = jnp.broadcast_to(holder_cache[None, None],
                             (2, NTP) + holder_cache.shape)
    fn2 = jax.jit(jax.shard_map(
        plain, mesh=mesh1,
        in_specs=(P("instance", "tp"), P("instance", "tp")),
        out_specs=(P("instance", "tp", None, None, None),
                   P("instance", "tp", None, None),
                   P("instance", "tp", None, None))))
    hlo_plain = fn2.lower(q_rep, c_rep).compile().as_text()
    cp_plain = parse_collectives(hlo_plain).result_bytes.get(
        "collective-permute", 0)
    ratio = cp_tpla / cp_plain
    print(f"  tpla permute bytes ratio: {ratio:.3f} (expect ~1/{NTP})")
    assert 0.15 < ratio < 0.40, ratio


if __name__ == "__main__":
    assert jax.device_count() == NI, jax.device_count()
    test_fanout_and_ring()
    test_pairwise()
    test_tpla_rank_pairing()
    print("DIST-ROUTING-OK")
