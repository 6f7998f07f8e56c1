"""Compile the served path's device programs for a described TPU v5e at
DeepSeek-V2 attention widths (128 heads, d_qk 576, d_v 512), ahead of
time and without a chip: what the TPU compiler refuses (a block that
breaks the tiling rule, a kernel that needs more VMEM than it may use)
fails here. Nothing runs, so nothing here says anything about results or
times. The topology is described only inside a fixture: only one process
at a time may load the TPU compiler's library."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_rotate import delta_rotate_band
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.mla_decode import mla_decode
from repro.kernels.softmax_merge import softmax_merge
from repro.kernels.sparse_select import sparse_select_decode
from repro.models.mla import absorbed_partial
from repro.serving.backends.jax_exec import DEEPSEEK_V2_MLA as CFG

H, D_QK, D_V = CFG.n_heads, CFG.d_qk, CFG.kv_lora_rank
S, B = 2048, 8


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip (it warns and recompiles)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_cases(spec):
    bf, f32 = jnp.bfloat16, jnp.float32
    q, ckv = spec((B, H, D_QK), bf), spec((B, S, D_QK), bf)
    return {
        "mla_decode": (lambda q, c: mla_decode(
            q, c, d_v=D_V, scale=CFG.scale, interpret=False), (q, ckv)),
        "sparse_select": (lambda q, c, i: sparse_select_decode(
            q, c, i, d_v=D_V, scale=CFG.scale, interpret=False),
            (q, ckv, spec((B, 16), jnp.int32))),
        "softmax_merge": (lambda o, m, l: softmax_merge(
            o, m, l, interpret=False),
            (spec((4, B, H, D_V), f32), spec((4, B, H), f32),
             spec((4, B, H), f32))),
        "flash_prefill": (lambda q, c: flash_prefill(
            q, c, d_v=D_V, scale=CFG.scale, interpret=False),
            (spec((2, 512, H, D_QK), bf), spec((2, S, D_QK), bf))),
        "delta_rotate": (lambda b, d: delta_rotate_band(
            b, d, head_dim=CFG.qk_rope_head_dim, interpret=False),
            (spec((S, CFG.qk_rope_head_dim), bf), spec((), f32))),
    }


@pytest.mark.parametrize("name", ["mla_decode", "sparse_select",
                                  "softmax_merge", "flash_prefill",
                                  "delta_rotate"])
def test_kernel_compiles_for_v5e(one_chip, name):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, args = _kernel_cases(spec)[name]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_absorbed_partial_compiles_for_v5e(one_chip):
    """The served holder compute: m_q 16 stacked decode rows against one
    2048-token chunk, bf16 operands, f32 accumulation."""
    compiled = _compile(
        lambda q, c: absorbed_partial(CFG, q, c),
        jax.ShapeDtypeStruct((16, H, D_QK), jnp.bfloat16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((S, D_QK), jnp.bfloat16, sharding=one_chip))
    o, m, l = compiled.out_info
    assert (o.shape, m.shape, l.shape) == ((16, H, D_V), (16, H), (16, H))
    assert o.dtype == m.dtype == l.dtype == jnp.float32


def test_fused_route_pair_program_compiles_on_2x2(topo):
    """The shard_map backend's fused one-home ROUTE program (ship ->
    holder-gated attend -> return) over a 4-chip "instance" mesh: the
    collectives are ppermutes and the attend is XLA's, not a kernel."""
    from repro.serving.backends.shard_map import AXIS, ShardMapExecBackend
    backend = ShardMapExecBackend(cfg=CFG, dtype=jnp.bfloat16)
    backend.mesh = Mesh(np.asarray(topo.devices), (AXIS,))
    sharded = NamedSharding(backend.mesh, P(AXIS))
    n, m_q = len(topo.devices), 16
    args = (jax.ShapeDtypeStruct((n * m_q, H, D_QK), jnp.bfloat16,
                                 sharding=sharded),
            jax.ShapeDtypeStruct((n * S, D_QK), jnp.bfloat16,
                                 sharding=sharded),
            jax.ShapeDtypeStruct((n * S,), jnp.bool_, sharding=sharded))
    compiled = backend._route_pair_program(holder=3, requester=0).lower(
        *args).compile()
    hlo = compiled.as_text()
    assert "collective-permute" in hlo
    assert "tpu_custom_call" not in hlo
