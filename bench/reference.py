"""The plain reference that decides `correct`, and its lower-precision
control. Imports nothing of the program under test.

A served request's output is single-instance attention in absorbed decode
form over the concatenation of its chunks: the latent entries are
[c_kv | k_rope] (d_qk wide), the values are the first d_v = kv_lora_rank
columns, and the softmax scale is 1/sqrt(qk_nope_head_dim +
qk_rope_head_dim). The reference computes it in float32 at `highest`
matmul precision from the same bf16 inputs that were served: the chunk
arrays the benchmark made from the seed, and the decode queries derived
from each request's query seed.
"""

from __future__ import annotations

import dataclasses
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Geometry:
    n_heads: int
    d_qk: int
    d_v: int
    scale: float

    @classmethod
    def of(cls, config: dict) -> "Geometry":
        rank = config["kv_lora_rank"]
        rope = config["qk_rope_head_dim"]
        return cls(n_heads=config["num_attention_heads"], d_qk=rank + rope,
                   d_v=rank,
                   scale=float(1.0 / np.sqrt(config["qk_nope_head_dim"]
                                             + rope)))


def query(geom: Geometry, query_seed: int, step: int, m_q: int, dtype):
    """The request's absorbed decode queries at `step`: (m_q, H, d_qk)
    normals keyed by crc32("q:<query_seed>") folded with the step number.
    This is the input derivation the serving backend documents for its
    queries; the benchmark re-derives it rather than reading the tensor
    the program made."""
    base = jax.random.PRNGKey(zlib.crc32(f"q:{query_seed}".encode()))
    key = jax.random.fold_in(base, step)
    return jax.random.normal(key, (m_q, geom.n_heads, geom.d_qk), dtype)


@partial(jax.jit, static_argnames=("d_v", "scale"))
def attend(q, ckv, *, d_v: int, scale: float):
    """float32 single-instance attention: q (m, H, d_qk), ckv (S, d_qk)
    -> o (m, H, d_v)."""
    q = q.astype(jnp.float32)
    c = ckv.astype(jnp.float32)
    logits = jnp.einsum("mhc,sc->mhs", q, c, precision=HIGHEST) * scale
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("mhs,sd->mhd", p, c[:, :d_v], precision=HIGHEST)


@partial(jax.jit, static_argnames=("d_v", "scale"))
def attend_fp8(q, ckv, *, d_v: int, scale: float):
    """The control: the same attention with the queries and the latent
    cache held in float8_e4m3fn, the step below the configuration's bf16
    that an fp8 cache would take. Softmax and sums stay in float32."""
    f8 = jnp.float8_e4m3fn
    return attend(q.astype(f8).astype(jnp.float32),
                  ckv.astype(f8).astype(jnp.float32), d_v=d_v, scale=scale)


def _dense(config: dict, request, step: int, chunk_arrays, dtype):
    geom = Geometry.of(config)
    q = query(geom, request.query_seed, step, request.m_q, dtype)
    return geom, q, jnp.concatenate(chunk_arrays)


def expected(config: dict, seed: int, request, step: int, chunk_ids,
             chunk_arrays, dtype):
    """The harness's default `expected` hook: `attend` over the request's
    concatenated chunks, with its queries at `step`. `seed` and
    `chunk_ids` are not needed: the arrays carry the seed's data in the
    request's chunk order."""
    geom, q, cat = _dense(config, request, step, chunk_arrays, dtype)
    return attend(q, cat, d_v=geom.d_v, scale=geom.scale)


def control(config: dict, seed: int, request, step: int, chunk_ids,
            chunk_arrays, dtype):
    """The harness's default `control` hook: `expected` in fp8
    (`attend_fp8`)."""
    geom, q, cat = _dense(config, request, step, chunk_arrays, dtype)
    return attend_fp8(q, cat, d_v=geom.d_v, scale=geom.scale)


def gap(got, want) -> float:
    """Largest absolute difference of one output against its reference,
    on the host, wherever the two arrays live."""
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))
