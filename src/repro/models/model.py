"""Composable model assembly: one ModelConfig drives all 10 assigned
architectures (dense GQA/MHA, MLA, MoE, SSM, hybrid, enc-dec, VLM).

Layer stacks are scan-over-layers (stacked params, lax.scan) so 60-96-layer
configs lower to compact HLO; remat is applied at block boundaries.

Step functions (consumed by launch/dryrun.py and the train loop):
  * forward / loss_fn      — training forward + chunked-CE loss
  * prefill                — forward returning the KV/latent caches
  * init_decode_state      — cache pytree (abstract or concrete)
  * decode_step            — one token against a seq_len cache (serve_step)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.distributed import policy as POL
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.module import KeyGen, Param, init_stacked, param, split


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | vlm | hybrid | ssm | audio
    n_layers: int
    d_model: int
    vocab: int
    # attention (gqa family)
    attn_type: str = "gqa"           # gqa | mla | none
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # mlp
    d_ff: int = 0
    mlp_kind: str = "swiglu"
    norm_kind: str = "rmsnorm"       # rmsnorm | layernorm
    # MLA
    mla: Optional[MLA.MLAConfig] = None
    # MoE
    moe: Optional[MOE.MoEConfig] = None
    first_k_dense: int = 0
    # SSM / hybrid
    ssm: Optional[SSM.Mamba2Config] = None
    hybrid_group: int = 0            # zamba2: shared attn after every group
    # enc-dec (whisper)
    encdec: bool = False
    n_enc_layers: int = 0
    enc_seq: int = 1500
    # vlm (llava)
    vlm_patches: int = 0
    # selection (paper technique: DSA-style top-k decode for long context)
    selection_k: int = 0
    # loss
    loss_chunk: int = 512
    remat: bool = True

    @property
    def attn_cfg(self) -> A.AttnConfig:
        return A.AttnConfig(self.d_model, self.n_heads, self.n_kv_heads,
                            self.head_dim, self.qkv_bias, self.qk_norm,
                            self.rope_theta,
                            use_rope=not self.encdec)

    @property
    def kv_bytes_token_layer(self) -> int:
        """FETCH-side payload coefficient for the predicate (§5.4)."""
        if self.attn_type == "mla":
            return self.mla.d_qk * 2
        if self.attn_type == "none":
            return 0
        return self.attn_cfg.kv_bytes_token_layer

    def norm_init(self):
        return (L.init_rmsnorm if self.norm_kind == "rmsnorm"
                else L.init_layernorm)

    def norm_apply(self):
        return L.rmsnorm if self.norm_kind == "rmsnorm" else L.layernorm


# ---------------------------------------------------------------------------
# MoE execution: under a mesh policy, run the expert layer inside shard_map
# (DESIGN.md §5): activations replicated over the expert (`model`) axis
# within a data shard, each shard computes its resident experts, one psum
# combines. Plain-GSPMD lowering of the sort-based dispatch replicates the
# (T*k, d) dispatch buffers and all-reduces them — measured 18.9 TB/device
# per step on qwen3-moe train_4k (EXPERIMENTS.md §Perf A2).
# ---------------------------------------------------------------------------

def _moe_call(p_moe, cfg: ModelConfig, x, ep_axis=None):
    from jax.sharding import PartitionSpec as P
    pol = POL.current()
    if pol is None or "model" not in pol.mesh.axis_names:
        y, aux = MOE.moe_apply(p_moe, cfg.moe, x, ep_axis)
        return y, aux
    mesh = pol.mesh
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    b_entry = (dp if len(dp) > 1 else dp[0]) \
        if dp and x.shape[0] % dp_size == 0 else None
    x_spec = P(b_entry, None, None)
    p_specs = {}
    for k in p_moe:
        if k == "router":
            p_specs[k] = P(None, None)
        elif k in ("gate", "up", "down"):
            p_specs[k] = P("model", None, None)      # expert-sharded stacks
        elif k in ("sh_gate", "sh_up"):
            p_specs[k] = P(None, "model")            # shared FFN width
        else:                                        # sh_down
            p_specs[k] = P("model", None)

    def f(pm, xx):
        y, aux = MOE.moe_apply(pm, cfg.moe, xx, ep_axis="model")
        axes = dp + (() if True else ())
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y, aux

    y, aux = jax.shard_map(
        f, mesh=mesh, in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P()))(p_moe, x)
    return y, aux


# ---------------------------------------------------------------------------
# Blocks (one layer each). Each block has: init(kg) -> params,
# fwd(p, x, pos) -> (x', cache_entries), dec(p, x, cache, pos, widx)
# -> (x', new_cache).
# ---------------------------------------------------------------------------

def _init_dense_block(kg, cfg: ModelConfig, moe_block: bool):
    ni = cfg.norm_init()
    p = {"ln1": ni(cfg.d_model), "ln2": ni(cfg.d_model)}
    if cfg.attn_type == "mla":
        p["attn"] = MLA.init_mla(kg, cfg.mla)
    else:
        p["attn"] = A.init_attn(kg, cfg.attn_cfg)
    if moe_block:
        p["moe"] = MOE.init_moe(kg, cfg.moe)
    else:
        p["mlp"] = L.init_mlp(kg, cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    return p


def _dense_block_fwd(p, cfg: ModelConfig, x, positions, moe_block: bool,
                     ep_axis=None):
    na = cfg.norm_apply()
    h = na(p["ln1"], x)
    if cfg.attn_type == "mla":
        attn_out, cache = MLA.mla_attention(p["attn"], cfg.mla, h, positions)
    else:
        attn_out, cache = A.attention(p["attn"], cfg.attn_cfg, h, positions)
    x = x + attn_out
    h = na(p["ln2"], x)
    if moe_block:
        mo, aux = _moe_call(p["moe"], cfg, h, ep_axis)
        return x + mo, cache, aux
    return x + L.mlp(p["mlp"], h, cfg.mlp_kind), cache, jnp.float32(0)


def _dense_block_dec(p, cfg: ModelConfig, x, cache, positions, widx,
                     moe_block: bool, ep_axis=None):
    na = cfg.norm_apply()
    h = na(p["ln1"], x)
    if cfg.attn_type == "mla":
        attn_out, new_cache = _mla_decode_cached(p["attn"], cfg, h, cache,
                                                 positions, widx)
    else:
        attn_out, new_cache = _gqa_decode_cached(p["attn"], cfg.attn_cfg, h,
                                                 cache, positions, widx)
    x = x + attn_out
    h = na(p["ln2"], x)
    if moe_block:
        mo, _ = _moe_call(p["moe"], cfg, h, ep_axis)
        return x + mo, new_cache
    return x + L.mlp(p["mlp"], h, cfg.mlp_kind), new_cache


def _gqa_decode_cached(p, acfg: A.AttnConfig, x, cache, positions, widx):
    """Write the new entry into the cache, then attend over the full cache."""
    k_cache, v_cache = cache
    q, k_new, v_new = A._project(p, acfg, x, x, positions, positions)
    k_cache = lax.dynamic_update_slice_in_dim(k_cache, k_new, widx, axis=1)
    v_cache = lax.dynamic_update_slice_in_dim(v_cache, v_new, widx, axis=1)
    out = A._sdpa(acfg, q, k_cache, v_cache, None)
    out = jnp.einsum("bshd,hdm->bsm", out, p["o"])
    return out, (k_cache, v_cache)


def _mla_decode_cached(p, cfg: ModelConfig, x, ckv_cache, positions, widx):
    """Absorbed MLA decode over the latent cache. With selection_k > 0,
    attends only the indexer's top-k entries (DSA regime, §5.4) — the
    sub-quadratic path that long_500k requires."""
    mcfg = cfg.mla
    q_nope, q_rope = MLA.project_q(p, mcfg, x, positions)
    q_abs = MLA.absorb_query(p, mcfg, q_nope, q_rope)     # (B,1,H,576)
    new_entry = MLA.latent_cache_entries(p, mcfg, x, positions)
    ckv_cache = lax.dynamic_update_slice_in_dim(ckv_cache, new_entry, widx,
                                                axis=1)
    if cfg.selection_k:
        # lightweight indexer: score = mean-head absorbed q . c^KV (latent
        # part); top-k tokens attended in place (no re-rotation — §3.3).
        qi = jnp.mean(q_abs[..., : mcfg.kv_lora_rank], axis=2)    # (B,1,dc)
        scores = jnp.einsum("bqc,bsc->bqs", qi,
                            ckv_cache[..., : mcfg.kv_lora_rank])
        _, sel = lax.top_k(scores[:, 0], cfg.selection_k)          # (B,k)
        sel_ckv = jnp.take_along_axis(ckv_cache, sel[..., None], axis=1)
        part = jax.vmap(lambda qb, cb: MLA.absorbed_partial(mcfg, qb, cb))(
            q_abs, sel_ckv)
    else:
        part = jax.vmap(lambda qb, cb: MLA.absorbed_partial(mcfg, qb, cb))(
            q_abs, ckv_cache)
    out = MLA.unabsorb_output(p, mcfg, part.o[..., : mcfg.kv_lora_rank]
                              .astype(x.dtype))
    return out, ckv_cache


# ---------------------------------------------------------------------------
# Stage runners: scan over stacked layer params.
# ---------------------------------------------------------------------------

def _scan_fwd(stacked, x, positions, block_fwd, remat=True, with_cache=True):
    f = jax.checkpoint(block_fwd) if remat else block_fwd

    def body(carry, lp):
        x = carry
        # sequence-parallel residual constraint (policy-controlled; no-op
        # without an installed policy)
        x = POL.constrain(x, "residual")
        x, cache, aux = f(lp, x)
        return x, (cache if with_cache else None, aux)

    x, (caches, auxs) = lax.scan(body, x, stacked)
    return x, caches, jnp.sum(auxs)


def _scan_dec(stacked, caches, x, block_dec):
    def body(carry, inp):
        x = carry
        lp, lc = inp
        x, nc = block_dec(lp, x, lc)
        return x, nc

    x, new_caches = lax.scan(body, x, (stacked, caches))
    return x, new_caches


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

def init_model(cfg: ModelConfig, key) -> dict:
    kg = KeyGen(key)
    p: Dict[str, Any] = {"embed": L.init_embed(kg, cfg.vocab, cfg.d_model),
                         "final_norm": cfg.norm_init()(cfg.d_model)}

    if cfg.family in ("dense", "vlm"):
        p["blocks"] = init_stacked(kg(), cfg.n_layers,
                                   lambda k: _init_dense_block(k, cfg, False))
    elif cfg.family == "moe":
        if cfg.first_k_dense:
            p["dense_blocks"] = init_stacked(
                kg(), cfg.first_k_dense,
                lambda k: _init_dense_block(k, cfg, False))
        p["blocks"] = init_stacked(
            kg(), cfg.n_layers - cfg.first_k_dense,
            lambda k: _init_dense_block(k, cfg, True))
    elif cfg.family == "ssm":
        p["blocks"] = init_stacked(
            kg(), cfg.n_layers,
            lambda k: {"ln": cfg.norm_init()(cfg.d_model),
                       "mamba": SSM.init_mamba2(k, cfg.ssm)})
    elif cfg.family == "hybrid":
        g = cfg.hybrid_group
        n_groups, rem = cfg.n_layers // g, cfg.n_layers % g
        p["groups"] = init_stacked(
            kg(), n_groups,
            lambda k: init_stacked(k(), g,
                                   lambda k2: {"ln": cfg.norm_init()(cfg.d_model),
                                               "mamba": SSM.init_mamba2(k2, cfg.ssm)}))
        if rem:
            p["rem"] = init_stacked(
                kg(), rem,
                lambda k: {"ln": cfg.norm_init()(cfg.d_model),
                           "mamba": SSM.init_mamba2(k, cfg.ssm)})
        # the SHARED attention block (one set of weights, reused per group —
        # Zamba2's shared transformer block, simplified: no per-invocation
        # LoRA, DESIGN.md §4)
        p["shared_attn"] = {"ln": cfg.norm_init()(cfg.d_model),
                            "attn": A.init_attn(kg, cfg.attn_cfg),
                            "ln2": cfg.norm_init()(cfg.d_model),
                            "mlp": L.init_mlp(kg, cfg.d_model, cfg.d_ff,
                                              cfg.mlp_kind)}
    elif cfg.family == "audio":
        enc_cfg = dataclasses.replace(cfg.attn_cfg, causal=False)
        p["enc_blocks"] = init_stacked(
            kg(), cfg.n_enc_layers,
            lambda k: {"ln1": cfg.norm_init()(cfg.d_model),
                       "attn": A.init_attn(k, enc_cfg),
                       "ln2": cfg.norm_init()(cfg.d_model),
                       "mlp": L.init_mlp(k, cfg.d_model, cfg.d_ff,
                                         cfg.mlp_kind)})
        p["enc_norm"] = cfg.norm_init()(cfg.d_model)
        p["blocks"] = init_stacked(
            kg(), cfg.n_layers,
            lambda k: {"ln1": cfg.norm_init()(cfg.d_model),
                       "attn": A.init_attn(k, cfg.attn_cfg),
                       "lnx": cfg.norm_init()(cfg.d_model),
                       "xattn": A.init_attn(k, cfg.attn_cfg),
                       "ln2": cfg.norm_init()(cfg.d_model),
                       "mlp": L.init_mlp(k, cfg.d_model, cfg.d_ff,
                                         cfg.mlp_kind)})
    else:
        raise ValueError(cfg.family)
    return p


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, batch):
    """tokens (+ stub modality embeddings) -> x (B, S, D), positions."""
    tokens = batch["tokens"]
    x = L.embed(params["embed"], tokens)
    if cfg.family == "vlm":
        # anyres frontend stub: precomputed patch embeddings prepended
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return x, positions


def forward(params, cfg: ModelConfig, batch, ep_axis=None,
            return_caches=False):
    """Training/prefill forward -> (logits, caches, aux_loss)."""
    if cfg.family == "audio":
        return _forward_audio(params, cfg, batch, return_caches)
    x, positions = _embed_inputs(params, cfg, batch)

    aux_total = jnp.float32(0)
    caches = {}
    if cfg.family in ("dense", "vlm"):
        fwd = lambda lp, h: _dense_block_fwd(lp, cfg, h, positions, False)
        x, c, _ = _scan_fwd(params["blocks"], x, positions, fwd, cfg.remat,
                            return_caches)
        caches["blocks"] = c
    elif cfg.family == "moe":
        if cfg.first_k_dense:
            fwd_d = lambda lp, h: _dense_block_fwd(lp, cfg, h, positions, False)
            x, c, _ = _scan_fwd(params["dense_blocks"], x, positions, fwd_d,
                                cfg.remat, return_caches)
            caches["dense_blocks"] = c
        fwd_m = lambda lp, h: _dense_block_fwd(lp, cfg, h, positions, True,
                                               ep_axis)
        x, c, aux = _scan_fwd(params["blocks"], x, positions, fwd_m,
                              cfg.remat, return_caches)
        caches["blocks"] = c
        aux_total = aux_total + aux
    elif cfg.family == "ssm":
        def fwd_s(lp, h):
            y, (hf, cs) = SSM.mamba2_forward(lp["mamba"], cfg.ssm,
                                             cfg.norm_apply()(lp["ln"], h))
            return h + y, (hf, cs), jnp.float32(0)
        x, c, _ = _scan_fwd(params["blocks"], x, positions, fwd_s, cfg.remat,
                            return_caches)
        caches["blocks"] = c
    elif cfg.family == "hybrid":
        x, caches = _forward_hybrid(params, cfg, x, positions, return_caches)
    logits = L.unembed(params["embed"],
                       cfg.norm_apply()(params["final_norm"], x))
    if cfg.family == "vlm":
        logits = logits[:, cfg.vlm_patches:]     # loss over text positions
    return logits, (caches if return_caches else None), aux_total


def _forward_hybrid(params, cfg: ModelConfig, x, positions, return_caches):
    na = cfg.norm_apply()

    def mamba_layer(lp, h):
        y, (hf, cs) = SSM.mamba2_forward(lp["mamba"], cfg.ssm, na(lp["ln"], h))
        return h + y, (hf, cs), jnp.float32(0)

    def group(gp, h):
        h, states, _ = _scan_fwd(gp, h, positions, mamba_layer, cfg.remat,
                                 return_caches)
        sa = params["shared_attn"]
        attn_out, kv = A.attention(sa["attn"], cfg.attn_cfg, na(sa["ln"], h),
                                   positions)
        h = h + attn_out
        h = h + L.mlp(sa["mlp"], na(sa["ln2"], h), cfg.mlp_kind)
        return h, (states, kv), jnp.float32(0)

    x, caches, _ = _scan_fwd(params["groups"], x, positions, group,
                             remat=False, with_cache=return_caches)
    rem_caches = None
    if "rem" in params:
        x, rem_caches, _ = _scan_fwd(params["rem"], x, positions, mamba_layer,
                                     cfg.remat, return_caches)
    return x, {"groups": caches, "rem": rem_caches}


def _forward_audio(params, cfg: ModelConfig, batch, return_caches):
    """Whisper-style enc-dec. batch: frame_embeds (B, S_enc, D) [conv
    frontend stub], tokens (B, S_dec)."""
    na = cfg.norm_apply()
    enc_cfg = dataclasses.replace(cfg.attn_cfg, causal=False)
    xe = batch["frame_embeds"]
    B, Se = xe.shape[:2]
    pos_e = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))

    def enc_block(lp, h):
        ao, _ = A.attention(lp["attn"], enc_cfg, na(lp["ln1"], h), pos_e)
        h = h + ao
        return h + L.mlp(lp["mlp"], na(lp["ln2"], h), cfg.mlp_kind), None, \
            jnp.float32(0)

    xe, _, _ = _scan_fwd(params["enc_blocks"], xe, pos_e, enc_block,
                         cfg.remat, with_cache=False)
    xe = na(params["enc_norm"], xe)

    xd = L.embed(params["embed"], batch["tokens"])
    Sd = xd.shape[1]
    pos_d = jnp.broadcast_to(jnp.arange(Sd, dtype=jnp.int32)[None], (B, Sd))

    def dec_block(lp, h):
        ao, self_kv = A.attention(lp["attn"], cfg.attn_cfg, na(lp["ln1"], h),
                                  pos_d)
        h = h + ao
        xo, cross_kv = A.attention(lp["xattn"], enc_cfg, na(lp["lnx"], h),
                                   pos_d, x_kv=xe, kv_positions=pos_e)
        h = h + xo
        return h + L.mlp(lp["mlp"], na(lp["ln2"], h), cfg.mlp_kind), \
            (self_kv, cross_kv), jnp.float32(0)

    xd, caches, _ = _scan_fwd(params["blocks"], xd, pos_d, dec_block,
                              cfg.remat, return_caches)
    logits = L.unembed(params["embed"], na(params["final_norm"], xd))
    return logits, ({"blocks": caches} if return_caches else None), \
        jnp.float32(0)


# ---------------------------------------------------------------------------
# Loss (chunked CE to bound the f32 logit footprint)
# ---------------------------------------------------------------------------

def loss_fn(params, cfg: ModelConfig, batch, ep_axis=None):
    logits, _, aux = forward(params, cfg, batch, ep_axis)
    targets = batch["targets"]
    if cfg.family == "vlm":
        pass                                  # logits already text-only
    B, S, V = logits.shape
    # largest chunk <= loss_chunk that divides S (VLM text spans etc.)
    n_chunks = max(1, S // min(cfg.loss_chunk, S))
    while S % n_chunks:
        n_chunks += 1
    chunk = S // n_chunks

    def ce_chunk(_, i):
        lg = lax.dynamic_slice_in_dim(logits, i * chunk, chunk, axis=1)
        tg = lax.dynamic_slice_in_dim(targets, i * chunk, chunk, axis=1)
        lg = lg.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
        return None, jnp.sum(lse - gold)

    _, losses = lax.scan(ce_chunk, None, jnp.arange(n_chunks))
    loss = jnp.sum(losses) / (B * chunk * n_chunks)
    return loss + 0.01 * aux


# ---------------------------------------------------------------------------
# Decode (serve_step): one token against a seq_len cache.
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, seq_len: int,
                      abstract: bool = False, dtype=jnp.bfloat16):
    """The cache pytree. abstract=True -> ShapeDtypeStructs (dry-run)."""
    mk = (lambda s, dt=dtype: jax.ShapeDtypeStruct(s, dt)) if abstract \
        else (lambda s, dt=dtype: jnp.zeros(s, dt))
    acfg = cfg.attn_cfg

    def gqa_cache(n_layers, s=seq_len):
        return (mk((n_layers, batch, s, acfg.n_kv_heads, acfg.hd)),
                mk((n_layers, batch, s, acfg.n_kv_heads, acfg.hd)))

    def mla_cache(n_layers):
        return mk((n_layers, batch, seq_len, cfg.mla.d_qk))

    def ssm_state(*lead):
        s = cfg.ssm
        return (mk(lead + (batch, s.n_heads, s.head_dim, s.d_state),
                   jnp.float32),
                mk(lead + (batch, s.d_conv - 1, s.d_inner + 2 * s.d_state)))

    if cfg.family in ("dense", "vlm"):
        n = cfg.n_layers
        return {"blocks": mla_cache(n) if cfg.attn_type == "mla"
                else gqa_cache(n)}
    if cfg.family == "moe":
        st = {}
        if cfg.first_k_dense:
            st["dense_blocks"] = (mla_cache(cfg.first_k_dense)
                                  if cfg.attn_type == "mla"
                                  else gqa_cache(cfg.first_k_dense))
        n = cfg.n_layers - cfg.first_k_dense
        st["blocks"] = mla_cache(n) if cfg.attn_type == "mla" else gqa_cache(n)
        return st
    if cfg.family == "ssm":
        return {"blocks": ssm_state(cfg.n_layers)}
    if cfg.family == "hybrid":
        g = cfg.hybrid_group
        ng, rem = cfg.n_layers // g, cfg.n_layers % g
        st = {"groups": ssm_state(ng, g), "shared_kv": gqa_cache(ng)}
        if rem:
            st["rem"] = ssm_state(rem)
        return st
    if cfg.family == "audio":
        n = cfg.n_layers
        return {"self": gqa_cache(n),
                "cross": gqa_cache(n, s=cfg.enc_seq)}
    raise ValueError(cfg.family)


def decode_step(params, cfg: ModelConfig, state, token, pos, widx,
                ep_axis=None):
    """token (B, 1) -> (logits (B, 1, V), new state). pos (B, 1) absolute
    positions; widx: static-shape cache write index (scalar int32)."""
    x = L.embed(params["embed"], token)
    na = cfg.norm_apply()

    if cfg.family in ("dense", "vlm", "moe"):
        def dec_dense(lp, h, lc):
            return _dense_block_dec(lp, cfg, h, lc, pos, widx, False)

        def dec_moe(lp, h, lc):
            return _dense_block_dec(lp, cfg, h, lc, pos, widx, True, ep_axis)

        new_state = {}
        if cfg.family == "moe" and cfg.first_k_dense:
            x, nc = _scan_dec(params["dense_blocks"], state["dense_blocks"],
                              x, dec_dense)
            new_state["dense_blocks"] = nc
        dec = dec_moe if cfg.family == "moe" else dec_dense
        x, nc = _scan_dec(params["blocks"], state["blocks"], x, dec)
        new_state["blocks"] = nc
    elif cfg.family == "ssm":
        def dec_s(lp, h, lc):
            y, ns = SSM.mamba2_decode(lp["mamba"], cfg.ssm,
                                      na(lp["ln"], h), lc)
            return h + y, ns
        x, nc = _scan_dec(params["blocks"], state["blocks"], x, dec_s)
        new_state = {"blocks": nc}
    elif cfg.family == "hybrid":
        x, new_state = _decode_hybrid(params, cfg, state, x, pos, widx)
    elif cfg.family == "audio":
        x, new_state = _decode_audio(params, cfg, state, x, pos, widx)
    else:
        raise ValueError(cfg.family)

    logits = L.unembed(params["embed"], na(params["final_norm"], x))
    return logits, new_state


def _decode_hybrid(params, cfg, state, x, pos, widx):
    na = cfg.norm_apply()

    def dec_mamba(lp, h, lc):
        y, ns = SSM.mamba2_decode(lp["mamba"], cfg.ssm, na(lp["ln"], h), lc)
        return h + y, ns

    def dec_group(carry, inp):
        h = carry
        gp, gstate, kv = inp
        h, ns = _scan_dec(gp, gstate, h, dec_mamba)
        sa = params["shared_attn"]
        ao, nkv = _gqa_decode_cached(sa["attn"], cfg.attn_cfg,
                                     na(sa["ln"], h), kv, pos, widx)
        h = h + ao
        h = h + L.mlp(sa["mlp"], na(sa["ln2"], h), cfg.mlp_kind)
        return h, (ns, nkv)

    x, (gstates, kvs) = lax.scan(dec_group, x,
                                 (params["groups"], state["groups"],
                                  state["shared_kv"]))
    new_state = {"groups": gstates, "shared_kv": kvs}
    if "rem" in params:
        x, ns = _scan_dec(params["rem"], state["rem"], x, dec_mamba)
        new_state["rem"] = ns
    return x, new_state


def _decode_audio(params, cfg, state, x, pos, widx):
    na = cfg.norm_apply()
    enc_cfg = dataclasses.replace(cfg.attn_cfg, causal=False)

    def dec(carry, inp):
        h = carry
        lp, self_kv, cross_kv = inp
        ao, nkv = _gqa_decode_cached(lp["attn"], cfg.attn_cfg,
                                     na(lp["ln1"], h), self_kv, pos, widx)
        h = h + ao
        ck, cv = cross_kv
        q = jnp.einsum("bsm,mhd->bshd", na(lp["lnx"], h), lp["xattn"]["q"])
        xo = A._sdpa(enc_cfg, q, ck, cv, None)
        h = h + jnp.einsum("bshd,hdm->bsm", xo, lp["xattn"]["o"])
        h = h + L.mlp(lp["mlp"], na(lp["ln2"], h), cfg.mlp_kind)
        return h, nkv

    x, nkvs = lax.scan(dec, x, (params["blocks"], state["self"],
                                state["cross"]))
    return x, {"self": nkvs, "cross": state["cross"]}


# ---------------------------------------------------------------------------
# Prefill: forward + caches, reshaped into decode-state layout.
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, batch, ep_axis=None):
    """Returns (last-token logits, caches). Cache layouts match forward's
    scan outputs: (L, B, S, ...) — the same leading-layer layout
    init_decode_state uses."""
    logits, caches, _ = forward(params, cfg, batch, ep_axis,
                                return_caches=True)
    return logits[:, -1:], caches
