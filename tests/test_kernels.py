"""Per-kernel validation (task spec): shape/dtype sweeps + hypothesis
property tests, assert_allclose against the ref.py pure-jnp oracles.
All kernels run in interpret mode on CPU (TPU is the target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.merge import merge_stacked
from repro.kernels.delta_rotate import delta_rotate_band, delta_rotate_ref
from repro.kernels.flash_prefill import flash_prefill, flash_prefill_ref
from repro.kernels.mla_decode import mla_decode, mla_decode_ref
from repro.kernels.softmax_merge import softmax_merge, softmax_merge_ref
from repro.kernels.sparse_select import (sparse_select_decode,
                                         sparse_select_ref)

SCALE = 1.0 / np.sqrt(192.0)


def _qc(key, B, H, S, D=64, d_v=48, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    q = jax.random.normal(k1, (B, H, D), dtype)
    ckv = jax.random.normal(k2, (B, S, D), dtype)
    return q, ckv


class TestMlaDecode:
    @pytest.mark.parametrize("B,H,S,bs", [(1, 4, 128, 64), (2, 16, 256, 128),
                                          (3, 8, 512, 512), (2, 128, 256, 64)])
    def test_shapes_sweep(self, B, H, S, bs):
        q, ckv = _qc(B * 1000 + S, B, H, S)
        got = mla_decode(q, ckv, d_v=48, scale=SCALE, block_s=bs)
        o, m, l = mla_decode_ref(q, ckv, 48, SCALE)
        np.testing.assert_allclose(np.asarray(got.o), np.asarray(o),
                                   atol=2e-6, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got.l), np.asarray(l),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got.m), np.asarray(m))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q, ckv = _qc(7, 2, 8, 256, dtype=dtype)
        got = mla_decode(q, ckv, d_v=48, scale=SCALE)
        o, m, l = mla_decode_ref(q, ckv, 48, SCALE)
        atol = 2e-6 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(got.o), np.asarray(o),
                                   atol=atol)

    def test_ragged_lengths(self, ):
        # residency mask: each batch row has its own valid cache length
        q, ckv = _qc(11, 3, 4, 256)
        lengths = jnp.asarray([64, 192, 256], jnp.int32)
        got = mla_decode(q, ckv, lengths, d_v=48, scale=SCALE, block_s=64)
        for b in range(3):
            o, m, l = mla_decode_ref(q[b:b+1], ckv[b:b+1, :int(lengths[b])],
                                     48, SCALE)
            np.testing.assert_allclose(np.asarray(got.o[b:b+1]),
                                       np.asarray(o), atol=2e-6, rtol=1e-5)

    def test_paper_payload_geometry(self):
        # the real wire geometry: d_qk=576, d_v=512, h=16 (V2-Lite)
        q, ckv = _qc(13, 2, 16, 512, D=576, d_v=512)
        got = mla_decode(q, ckv, d_v=512, scale=SCALE)
        o, m, l = mla_decode_ref(q, ckv, 512, SCALE)
        np.testing.assert_allclose(np.asarray(got.o), np.asarray(o),
                                   atol=5e-6, rtol=1e-5)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 8), st.integers(1, 8))
    def test_property_random_shapes(self, B, H, nblk):
        S = 64 * nblk
        q, ckv = _qc(B * 100 + H * 10 + nblk, B, H, S)
        got = mla_decode(q, ckv, d_v=48, scale=SCALE, block_s=64)
        o, m, l = mla_decode_ref(q, ckv, 48, SCALE)
        np.testing.assert_allclose(np.asarray(got.o), np.asarray(o),
                                   atol=2e-6, rtol=1e-5)


class TestSparseSelect:
    @pytest.mark.parametrize("B,H,S,KB", [(1, 4, 512, 4), (2, 16, 1024, 8),
                                          (2, 128, 2048, 32)])
    def test_shapes_sweep(self, B, H, S, KB):
        q, ckv = _qc(B * 31 + KB, B, H, S)
        rng = np.random.RandomState(B + KB)
        idx = jnp.asarray(
            np.stack([np.sort(rng.choice(S // 64, KB, replace=False))
                      for _ in range(B)]))
        got = sparse_select_decode(q, ckv, idx, d_v=48, scale=SCALE)
        o, m, l = sparse_select_ref(q, ckv, idx, 48, 64, SCALE)
        np.testing.assert_allclose(np.asarray(got.o), np.asarray(o),
                                   atol=2e-6, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got.l), np.asarray(l),
                                   rtol=1e-5)

    def test_selection_budget_invariance(self):
        # §6.3: cost tracks the selection budget, not the store size —
        # and the result only depends on the selected entries.
        B, H, KB = 1, 4, 4
        q, ckv_small = _qc(17, B, H, 512)
        pad = jax.random.normal(jax.random.PRNGKey(99), (B, 1536, 64))
        ckv_big = jnp.concatenate([ckv_small, pad], axis=1)
        idx = jnp.asarray([[0, 2, 5, 7]])
        a = sparse_select_decode(q, ckv_small, idx, d_v=48, scale=SCALE)
        b = sparse_select_decode(q, ckv_big, idx, d_v=48, scale=SCALE)
        np.testing.assert_allclose(np.asarray(a.o), np.asarray(b.o),
                                   atol=1e-6)

    def test_matches_dense_over_selected_set(self):
        # kernel == dense decode over the gathered selection (§3.3)
        q, ckv = _qc(23, 2, 8, 512)
        idx = jnp.asarray([[1, 3], [0, 7]])
        got = sparse_select_decode(q, ckv, idx, d_v=48, scale=SCALE)
        for b in range(2):
            blocks = ckv[b].reshape(-1, 64, 64)
            sel = blocks[np.asarray(idx[b])].reshape(1, -1, 64)
            o, m, l = mla_decode_ref(q[b:b+1], sel, 48, SCALE)
            np.testing.assert_allclose(np.asarray(got.o[b:b+1]),
                                       np.asarray(o), atol=2e-6, rtol=1e-5)


class TestDeltaRotate:
    @pytest.mark.parametrize("S,d_r", [(128, 16), (1024, 64), (2048, 64)])
    def test_matches_ref(self, S, d_r):
        band = jax.random.normal(jax.random.PRNGKey(S), (S, d_r))
        for delta in (0, 1, 1000):
            got = delta_rotate_band(band, jnp.float32(delta), head_dim=d_r)
            ref = delta_rotate_ref(band, jnp.float32(delta), d_r)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-5)

    def test_splice_correctness_via_kernel(self):
        # end-to-end: core.splice with the Pallas rotate_fn re-homes exactly
        from repro.core.splice import splice_delta_rotate
        from repro.models import mla as M
        from repro.models.module import KeyGen, split
        cfg = M.MLAConfig(d_model=128, n_heads=4, kv_lora_rank=32,
                          qk_nope_head_dim=16, qk_rope_head_dim=16,
                          v_head_dim=16)
        params, _ = split(M.init_mla(KeyGen(jax.random.PRNGKey(0)), cfg,
                                     dtype=jnp.float32))
        x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (1, 64, 128))
        pos = jnp.arange(64)[None]
        cached = M.latent_cache_entries(params, cfg, x, pos)
        rot = lambda band, d: delta_rotate_band(
            band[0], jnp.float32(d), head_dim=cfg.qk_rope_head_dim)[None]
        spliced = splice_delta_rotate(cached, 77, cfg, rotate_fn=rot)
        native = M.latent_cache_entries(params, cfg, x, pos + 77)
        np.testing.assert_allclose(np.asarray(spliced), np.asarray(native),
                                   atol=2e-5)


class TestSoftmaxMerge:
    @pytest.mark.parametrize("M,B,H,dv", [(2, 1, 4, 32), (8, 3, 16, 64),
                                          (16, 2, 8, 128)])
    def test_matches_ref(self, M, B, H, dv):
        k = jax.random.PRNGKey(M * 100 + B)
        ks = jax.random.split(k, 3)
        o = jax.random.normal(ks[0], (M, B, H, dv))
        m = jax.random.normal(ks[1], (M, B, H))
        l = jax.nn.softplus(jax.random.normal(ks[2], (M, B, H))) + 0.1
        got = softmax_merge(o, m, l)
        ref = softmax_merge_ref(o, m, l)
        np.testing.assert_allclose(np.asarray(got.o), np.asarray(ref.o),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.l), np.asarray(ref.l),
                                   rtol=1e-6)

    def test_identity_slots(self):
        # zero-weight identity partials (empty holders) are no-ops (§3.3)
        o = jnp.stack([jnp.ones((1, 2, 4)), jnp.zeros((1, 2, 4))])
        m = jnp.stack([jnp.zeros((1, 2)), jnp.full((1, 2), -jnp.inf)])
        l = jnp.stack([jnp.ones((1, 2)), jnp.zeros((1, 2))])
        got = softmax_merge(o, m, l)
        np.testing.assert_allclose(np.asarray(got.o), 1.0)
        np.testing.assert_allclose(np.asarray(got.l), 1.0)

    def test_kernel_equals_routed_oracle(self):
        # merge(kernel partials from disjoint shards) == full attention
        q, ckv = _qc(29, 2, 8, 512)
        p1 = mla_decode(q, ckv[:, :256], d_v=48, scale=SCALE, block_s=64)
        p2 = mla_decode(q, ckv[:, 256:], d_v=48, scale=SCALE, block_s=64)
        merged = softmax_merge(jnp.stack([p1.o, p2.o]),
                               jnp.stack([p1.m, p2.m]),
                               jnp.stack([p1.l, p2.l]))
        o, m, l = mla_decode_ref(q, ckv, 48, SCALE)
        np.testing.assert_allclose(np.asarray(merged.o), np.asarray(o),
                                   atol=2e-6, rtol=1e-5)


class TestFlashPrefill:
    @pytest.mark.parametrize("B,Sq,Sk,H", [(1, 64, 64, 2), (2, 128, 256, 4),
                                           (1, 256, 256, 8)])
    def test_causal_matches_ref(self, B, Sq, Sk, H):
        k1, k2 = jax.random.split(jax.random.PRNGKey(Sq + Sk))
        q = jax.random.normal(k1, (B, Sq, H, 64))
        ckv = jax.random.normal(k2, (B, Sk, 64))
        got = flash_prefill(q, ckv, d_v=48, scale=SCALE, block_q=64,
                            block_k=64)
        ref = flash_prefill_ref(q, ckv, 48, SCALE)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=3e-6, rtol=1e-5)

    def test_block_shape_invariance(self):
        # tiling must not change the math
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 4, 64))
        ckv = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64))
        outs = [flash_prefill(q, ckv, d_v=48, scale=SCALE, block_q=bq,
                              block_k=bk)
                for bq, bk in ((64, 64), (128, 256), (256, 128))]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       atol=2e-6, rtol=1e-5)


class TestInterpretSelection:
    @pytest.mark.parametrize("platform,want", [("cpu", True),
                                               ("tpu", False)])
    def test_cpu_interprets_tpu_compiles(self, monkeypatch, platform, want):
        from repro.kernels import common
        monkeypatch.setattr(common.jax, "default_backend", lambda: platform)
        assert common.use_interpret() is want

    def test_other_platform_raises(self, monkeypatch):
        from repro.kernels import common
        monkeypatch.setattr(common.jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            common.use_interpret()
