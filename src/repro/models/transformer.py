"""Unified decoder-only transformer covering the dense / MoE / VLM
assigned architectures:

  * GQA attention with RoPE / M-RoPE (Qwen2-VL 3-D sections), optional QKV
    bias, optional sliding window (ring-buffer decode cache).
  * SwiGLU / GELU MLPs, RMSNorm / LayerNorm.
  * Mixture-of-Experts with sort-based capacity dispatch (Mixtral softmax
    top-2; DeepSeek sigmoid top-8 + shared experts), switch-style
    load-balance auxiliary loss.
  * DeepSeek-V3 MLA: low-rank Q/KV projections, decoupled RoPE key, latent
    KV cache with *absorbed* decode (scores and values computed in the
    kv_lora latent space — the cache stores [B, S, kv_lora + rope] only).
  * Multi-token prediction (MTP) auxiliary head (DeepSeek-V3).
  * Token or precomputed-embedding inputs (VLM patch-embedding stub).

Layers are stacked ([L, ...] parameters) and executed with lax.scan;
training bodies are wrapped in jax.checkpoint (full remat) so 32k-token
activations never live across layers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.muon import ParamMeta
from repro.obs.trace import sub_span

from .common import (apply_rope, attention, chunked_softmax_xent,
                     decode_attention, embed_init, layer_norm, logits_last,
                     matrix_init, rms_norm, vector_init)


# ------------------------------------------------------------------ builders

class ParamBuilder:
    """Accumulates (params, metas) trees with identical structure."""

    def __init__(self, key: jax.Array, dtype):
        self.key = key
        self.dtype = dtype
        self.params: dict = {}
        self.metas: dict = {}

    def sub(self) -> jax.Array:
        self.key, k = jax.random.split(self.key)
        return k

    def matrix(self, path: str, in_dim: int, out_dim: int,
               stack: tuple[int, ...] = (), scale: float | None = None):
        p, m = matrix_init(self.sub(), in_dim, out_dim, self.dtype,
                           stack=stack, scale=scale)
        self._set(path, p, m)

    def vector(self, path: str, dim: int, stack: tuple[int, ...] = (),
               value: float | None = None):
        p, m = vector_init(self.sub(), dim, self.dtype, stack=stack,
                           value=value)
        self._set(path, p, m)

    def embed(self, path: str, vocab: int, dim: int):
        p, m = embed_init(self.sub(), vocab, dim, self.dtype)
        self._set(path, p, m)

    def _set(self, path: str, p, m):
        parts = path.split("/")
        d_p, d_m = self.params, self.metas
        for k in parts[:-1]:
            d_p = d_p.setdefault(k, {})
            d_m = d_m.setdefault(k, {})
        d_p[parts[-1]] = p
        d_m[parts[-1]] = m


def _norm(cfg: ArchConfig, p: dict, prefix: str, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, p[prefix + "_w"], p[prefix + "_b"], cfg.norm_eps)
    return rms_norm(x, p[prefix + "_w"], cfg.norm_eps)


def _add_norm_params(b: ParamBuilder, cfg: ArchConfig, path: str,
                     dim: int, stack=()):
    b.vector(path + "_w", dim, stack=stack, value=1.0)
    if cfg.norm == "layernorm":
        b.vector(path + "_b", dim, stack=stack, value=0.0)


def _act(cfg: ArchConfig, gate: jax.Array | None, up: jax.Array) -> jax.Array:
    if cfg.act in ("swiglu",):
        return jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    if cfg.act == "geglu":
        return jax.nn.gelu(gate.astype(jnp.float32)).astype(up.dtype) * up
    return jax.nn.gelu(up.astype(jnp.float32)).astype(up.dtype)


def _gated(cfg: ArchConfig) -> bool:
    return cfg.act in ("swiglu", "geglu")


def _add_mlp_params(b: ParamBuilder, cfg: ArchConfig, path: str, d: int,
                    ff: int, stack=()):
    if _gated(cfg):
        b.matrix(path + "/w_gate", d, ff, stack=stack)
    b.matrix(path + "/w_up", d, ff, stack=stack)
    b.matrix(path + "/w_down", ff, d, stack=stack,
             scale=1.0 / math.sqrt(ff))


def _mlp(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    up = x @ p["w_up"]
    gate = x @ p["w_gate"] if _gated(cfg) else None
    return _act(cfg, gate, up) @ p["w_down"]


# ----------------------------------------------------------------- attention

def _add_attn_params(b: ParamBuilder, cfg: ArchConfig, path: str, stack=()):
    d, hd = cfg.d_model, cfg.hd
    if cfg.mla is not None:
        mla = cfg.mla
        qk = mla.qk_nope + mla.qk_rope
        b.matrix(path + "/q_a", d, mla.q_lora, stack=stack)
        b.vector(path + "/q_norm_w", mla.q_lora, stack=stack, value=1.0)
        b.matrix(path + "/q_b", mla.q_lora, cfg.n_heads * qk, stack=stack)
        b.matrix(path + "/kv_a", d, mla.kv_lora + mla.qk_rope, stack=stack)
        b.vector(path + "/kv_norm_w", mla.kv_lora, stack=stack, value=1.0)
        b.matrix(path + "/kv_b", mla.kv_lora,
                 cfg.n_heads * (mla.qk_nope + mla.v_dim), stack=stack)
        b.matrix(path + "/wo", cfg.n_heads * mla.v_dim, d, stack=stack,
                 scale=1.0 / math.sqrt(cfg.n_heads * mla.v_dim))
        return
    b.matrix(path + "/wq", d, cfg.n_heads * hd, stack=stack)
    b.matrix(path + "/wk", d, cfg.n_kv_heads * hd, stack=stack)
    b.matrix(path + "/wv", d, cfg.n_kv_heads * hd, stack=stack)
    b.matrix(path + "/wo", cfg.n_heads * hd, d, stack=stack,
             scale=1.0 / math.sqrt(cfg.n_heads * hd))
    if cfg.qkv_bias:
        for n in ("bq", "bk", "bv"):
            dim = cfg.n_heads * hd if n == "bq" else cfg.n_kv_heads * hd
            b.vector(path + f"/{n}", dim, stack=stack, value=0.0)


def _rope(cfg: ArchConfig, x: jax.Array, pos: jax.Array) -> jax.Array:
    if cfg.rope in ("none", "learned"):
        return x
    sections = cfg.mrope_sections if cfg.rope == "mrope" else None
    return apply_rope(x, pos, base=cfg.rope_base, mrope_sections=sections)


def _gqa_attn(cfg: ArchConfig, p: dict, h: jax.Array, pos: jax.Array,
              cache: dict | None, t, mode: str, causal: bool = True):
    """Standard GQA attention. Returns (out, new_cache_entries)."""
    b_, s, _ = h.shape
    hd = cfg.hd
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b_, s, cfg.n_heads, hd)
    k = k.reshape(b_, s, cfg.n_kv_heads, hd)
    v = v.reshape(b_, s, cfg.n_kv_heads, hd)
    q = _rope(cfg, q, pos)
    k = _rope(cfg, k, pos)

    if mode in ("full", "prefill"):
        out = attention(q, k, v, causal=causal, window=cfg.window)
        new_cache = None
        if mode == "prefill":
            cap = cache["k"].shape[1]
            if cap >= s:
                kc = jnp.pad(k, ((0, 0), (0, cap - s), (0, 0), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, cap - s), (0, 0), (0, 0)))
            else:  # ring buffer: last `cap` tokens at slot (abs_pos % cap)
                idx = (jnp.arange(s - cap, s)) % cap
                kc = jnp.zeros_like(cache["k"]).at[:, idx].set(k[:, -cap:])
                vc = jnp.zeros_like(cache["v"]).at[:, idx].set(v[:, -cap:])
            new_cache = {"k": kc.astype(cache["k"].dtype),
                         "v": vc.astype(cache["v"].dtype)}
        return out, new_cache

    # decode: write the new kv at slot t (ring for windowed caches)
    cap = cache["k"].shape[1]
    slot = jnp.asarray(t, jnp.int32) % cap
    kc = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    kv_len = jnp.minimum(jnp.asarray(t, jnp.int32) + 1, cap)
    out = decode_attention(q, kc, vc, kv_len=kv_len)
    return out, {"k": kc, "v": vc}


def _mla_attn(cfg: ArchConfig, p: dict, h: jax.Array, pos: jax.Array,
              cache: dict | None, t, mode: str):
    """DeepSeek-V3 multi-head latent attention."""
    mla = cfg.mla
    b_, s, _ = h.shape
    H, nope, rope_d, vd = cfg.n_heads, mla.qk_nope, mla.qk_rope, mla.v_dim
    scale = 1.0 / math.sqrt(nope + rope_d)

    q = rms_norm(h @ p["q_a"], p["q_norm_w"], cfg.norm_eps) @ p["q_b"]
    q = q.reshape(b_, s, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, pos, base=cfg.rope_base)

    kv_a = h @ p["kv_a"]
    c_kv = rms_norm(kv_a[..., :mla.kv_lora], p["kv_norm_w"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., None, mla.kv_lora:], pos,
                        base=cfg.rope_base)  # [B,S,1,rope]

    if mode in ("full", "prefill"):
        kv = (c_kv @ p["kv_b"]).reshape(b_, s, H, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b_, s, H, rope_d))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        # chunked attention wants matching k/v head dims: zero-pad v
        vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nope + rope_d - vd)))
        out = attention(qf, k, vpad, causal=True, softmax_scale=scale)
        out = out[..., :vd].reshape(b_, s, H * vd) @ p["wo"]
        new_cache = None
        if mode == "prefill":
            cap = cache["c_kv"].shape[1]
            ckv = jnp.pad(c_kv, ((0, 0), (0, cap - s), (0, 0)))
            krp = jnp.pad(k_rope[:, :, 0], ((0, 0), (0, cap - s), (0, 0)))
            new_cache = {"c_kv": ckv.astype(cache["c_kv"].dtype),
                         "k_rope": krp.astype(cache["k_rope"].dtype)}
        return out, new_cache

    # absorbed decode: scores and values in the kv_lora latent space.
    cap = cache["c_kv"].shape[1]
    slot = jnp.asarray(t, jnp.int32) % cap
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), slot, axis=1)
    krp = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope[:, :, 0].astype(cache["k_rope"].dtype),
        slot, axis=1)
    kv_len = jnp.minimum(jnp.asarray(t, jnp.int32) + 1, cap)

    w_kv = p["kv_b"].reshape(mla.kv_lora, H, nope + vd)
    w_uk, w_uv = w_kv[..., :nope], w_kv[..., nope:]
    # absorb W_uk into the query: q_lat [B,1,H,kv_lora]; all cache-sized
    # einsums accumulate in f32 without materialising f32 cache copies
    q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    s_lat = jnp.einsum("bshl,bkl->bhsk", q_lat.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bshr,bkr->bhsk", q_rope, krp,
                        preferred_element_type=jnp.float32)
    scores = (s_lat + s_rope) * scale
    mask = jnp.arange(cap)[None, None, None, :] < kv_len
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhsk,bkl->bshl", w.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)
    o = jnp.einsum("bshl,lhv->bshv", o_lat.astype(h.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    out = o.reshape(b_, s, H * vd).astype(h.dtype) @ p["wo"]
    return out, {"c_kv": ckv, "k_rope": krp}


# ----------------------------------------------------------------------- MoE

def _add_moe_params(b: ParamBuilder, cfg: ArchConfig, path: str, stack=()):
    moe = cfg.moe
    d = cfg.d_model
    b.matrix(path + "/router", d, moe.n_experts, stack=stack)
    estack = stack + (moe.n_experts,)
    if _gated(cfg):
        b.matrix(path + "/e_gate", d, moe.d_expert, stack=estack)
    b.matrix(path + "/e_up", d, moe.d_expert, stack=estack)
    b.matrix(path + "/e_down", moe.d_expert, d, stack=estack,
             scale=1.0 / math.sqrt(moe.d_expert))
    if moe.n_shared:
        _add_mlp_params(b, cfg, path + "/shared", d,
                        moe.n_shared * moe.d_expert, stack=stack)


MOE_COMBINE_F32 = False   # pre-§Perf-A1 behaviour toggle (see _moe_ffn)


def moe_capacity(moe, n_tokens: int) -> int:
    c = int(math.ceil(moe.top_k * n_tokens * moe.capacity_factor
                      / moe.n_experts))
    return max(1, min(c, n_tokens))


def _moe_ffn(cfg: ArchConfig, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sort-based capacity-dispatch MoE. x [B,S,D] -> (out, aux_loss)."""
    moe = cfg.moe
    b_, s, d = x.shape
    T, E, K = b_ * s, moe.n_experts, moe.top_k
    C = moe_capacity(moe, T)
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    if moe.n_shared:  # DeepSeek-style sigmoid gate
        probs = jax.nn.sigmoid(logits)
    else:             # Mixtral-style softmax gate
        probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, K)
    weights = top_vals / (jnp.sum(top_vals, axis=-1, keepdims=True) + 1e-9)

    # switch-style load-balance auxiliary loss
    me = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_idx[:, 0], E), axis=0)
    aux = E * jnp.sum(me * ce)

    # sort-based dispatch: assignments [T*K] sorted by expert id
    a = top_idx.reshape(-1)
    w = weights.reshape(-1)
    order = jnp.argsort(a, stable=True)
    tok_s = (order // K).astype(jnp.int32)
    w_s = w[order]
    counts = jnp.zeros((E,), jnp.int32).at[a].add(1)
    starts = jnp.cumsum(counts) - counts
    grid = starts[:, None] + jnp.arange(C)[None, :]          # [E, C]
    valid = jnp.arange(C)[None, :] < counts[:, None]
    grid = jnp.clip(grid, 0, T * K - 1)
    tok_ec = tok_s[grid]                                     # [E, C]
    w_ec = jnp.where(valid, w_s[grid], 0.0)

    xin = xf[tok_ec]                                         # [E, C, D]
    up = jnp.einsum("ecd,edf->ecf", xin, p["e_up"])
    if _gated(cfg):
        gate = jnp.einsum("ecd,edf->ecf", xin, p["e_gate"])
        hmid = _act(cfg, gate, up)
    else:
        hmid = _act(cfg, None, up)
    out_ec = jnp.einsum("ecf,efd->ecd", hmid, p["e_down"])

    # §Perf iteration A1: the combine scatter crosses the expert-parallel
    # boundary (all-to-all at scale) — send it in the model dtype, not
    # f32, and weight before the move. Top-k partial sums in bf16 are
    # fine (<= 9 addends). MOE_COMBINE_F32 restores the pre-A1 behaviour
    # (used by the perf-iteration measurements).
    acc_dt = jnp.float32 if MOE_COMBINE_F32 else x.dtype
    contrib = (out_ec * w_ec[..., None].astype(out_ec.dtype)).astype(acc_dt)
    out = jnp.zeros((T, d), acc_dt).at[tok_ec.reshape(-1)].add(
        contrib.reshape(-1, d))
    out = out.astype(x.dtype)
    if moe.n_shared:
        out = out + _mlp(cfg, p["shared"], xf)
    return out.reshape(b_, s, d), aux


# -------------------------------------------------------------------- blocks

def _block(cfg: ArchConfig, p: dict, x: jax.Array, pos: jax.Array,
           cache: dict | None, t, mode: str, is_moe: bool):
    attn_fn = _mla_attn if cfg.mla is not None else _gqa_attn
    with sub_span("lm.attn"):
        h = _norm(cfg, p, "ln1", x)
        a_out, new_cache = attn_fn(cfg, p["attn"], h, pos, cache, t, mode)
        if cfg.mla is None:
            b_, s = x.shape[:2]
            a_out = (a_out.reshape(b_, s, cfg.n_heads * cfg.hd)
                     @ p["attn"]["wo"])
        x = x + a_out
    with sub_span("lm.mlp"):
        h = _norm(cfg, p, "ln2", x)
        if is_moe:
            f_out, aux = _moe_ffn(cfg, p["moe"], h)
        else:
            f_out, aux = _mlp(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)
        x = x + f_out
    return x, new_cache, aux


# ---------------------------------------------------------------- the model

class Transformer:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        moe = cfg.moe
        self.n_dense = cfg.moe_start_layer if moe else cfg.n_layers
        self.n_moe = cfg.n_layers - self.n_dense if moe else 0

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array):
        cfg = self.cfg
        b = ParamBuilder(key, jnp.dtype(cfg.dtype))
        b.embed("embed", cfg.vocab, cfg.d_model)
        if cfg.rope == "learned":
            b.embed("pos_embed", cfg.max_position, cfg.d_model)
        if not cfg.tied_embeddings:
            b.matrix("unembed", cfg.d_model, cfg.vocab,
                     scale=1.0 / math.sqrt(cfg.d_model))
            # unembed trains with the sign LMO (Scion's embedding treatment)
            b.metas["unembed"] = ParamMeta("sign", 1.0, 0)
        _add_norm_params(b, cfg, "final_ln", cfg.d_model)

        def add_blocks(name: str, n: int, is_moe: bool, ff: int):
            if n == 0:
                return
            stack = (n,)
            _add_norm_params(b, cfg, f"{name}/ln1", cfg.d_model, stack)
            _add_norm_params(b, cfg, f"{name}/ln2", cfg.d_model, stack)
            _add_attn_params(b, cfg, f"{name}/attn", stack)
            if is_moe:
                _add_moe_params(b, cfg, f"{name}/moe", stack)
            else:
                _add_mlp_params(b, cfg, f"{name}/mlp", cfg.d_model, ff, stack)

        dense_ff = cfg.dense_ff if cfg.dense_ff else cfg.d_ff
        add_blocks("dense_blocks", self.n_dense, False, dense_ff)
        add_blocks("moe_blocks", self.n_moe, True, 0)
        if cfg.mtp:
            b.matrix("mtp/proj", 2 * cfg.d_model, cfg.d_model)
            _add_norm_params(b, cfg, "mtp/ln_h", cfg.d_model)
            _add_norm_params(b, cfg, "mtp/ln_e", cfg.d_model)
            add_blocks("mtp/block", 1, False, dense_ff)
        return b.params, b.metas

    # -------------------------------------------------------------- plumbing
    def _stacks(self, params: dict):
        out = []
        if self.n_dense:
            out.append(("dense_blocks", params["dense_blocks"], False))
        if self.n_moe:
            out.append(("moe_blocks", params["moe_blocks"], True))
        return out

    def _run(self, params: dict, x: jax.Array, pos: jax.Array,
             cache: dict | None, t, mode: str, remat: bool):
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        new_cache = {} if cache is not None else None
        for name, stack_p, is_moe in self._stacks(params):
            def body(carry, xs, is_moe=is_moe):
                x, aux = carry
                p, c = xs
                x, nc, a = _block(cfg, p, x, pos, c, t, mode, is_moe)
                return (x, aux + a), nc

            if remat and mode == "full":
                body = jax.checkpoint(body)
            c_stack = cache[name] if cache is not None else None
            (x, aux_total), nc = jax.lax.scan(
                body, (x, aux_total), (stack_p, c_stack))
            if new_cache is not None:
                new_cache[name] = nc
        x = _norm(cfg, params, "final_ln", x)
        return x, new_cache, aux_total

    def _embed_in(self, params: dict, batch: dict):
        cfg = self.cfg
        with sub_span("lm.vocab"):
            if "embeds" in batch:
                x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
                pos = batch["pos"]
            else:
                x = params["embed"][batch["tokens"] if "tokens" in batch
                                    else batch["token"]]
                s = x.shape[1]
                pos = jnp.broadcast_to(jnp.arange(s)[None], x.shape[:2])
                if cfg.rope == "mrope":
                    pos = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
            if cfg.rope == "learned":
                x = x + params["pos_embed"][
                    jnp.clip(pos, 0, cfg.max_position - 1)]
        return x, pos

    def _unembed(self, params: dict):
        if self.cfg.tied_embeddings:
            return params["embed"].T
        return params["unembed"]

    # ------------------------------------------------------------------ loss
    def loss(self, params: dict, batch: dict, *, remat: bool = True):
        cfg = self.cfg
        x, pos = self._embed_in(params, batch)
        h, _, aux = self._run(params, x, pos, None, None, "full", remat)
        with sub_span("lm.vocab"):
            out = chunked_softmax_xent(h, self._unembed(params),
                                       batch["labels"])
        if cfg.moe:
            out = out + 0.01 * aux / max(self.n_moe, 1)
        if cfg.mtp and "tokens" in batch:
            out = out + 0.3 * self._mtp_loss(params, h, batch)
        return out

    def _mtp_loss(self, params: dict, h: jax.Array, batch: dict):
        """DeepSeek-V3 MTP: one extra block predicts token t+2 from
        (h_t, embed(token_{t+1}))."""
        cfg = self.cfg
        p = params["mtp"]
        tok_next = batch["tokens"][:, 1:]
        e = params["embed"][tok_next]
        comb = jnp.concatenate(
            [_norm(cfg, p, "ln_h", h[:, :-1]),
             _norm(cfg, p, "ln_e", e)], axis=-1) @ p["proj"]
        s = comb.shape[1]
        pos = jnp.broadcast_to(jnp.arange(s)[None], comb.shape[:2])
        blk = jax.tree.map(lambda a: a[0], p["block"])
        hm, _, _ = _block(cfg, blk, comb, pos, None, None, "full", False)
        labels_mtp = batch["labels"][:, 1:]
        mask = jnp.ones_like(labels_mtp, dtype=bool).at[:, -1].set(False)
        return chunked_softmax_xent(hm, self._unembed(params), labels_mtp,
                                    mask=mask)

    # ----------------------------------------------------------------- cache
    def _cache_entry(self, batch_size: int, cap: int):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        if cfg.mla is not None:
            return {"c_kv": ((batch_size, cap, cfg.mla.kv_lora), dt),
                    "k_rope": ((batch_size, cap, cfg.mla.qk_rope), dt)}
        return {"k": ((batch_size, cap, cfg.n_kv_heads, cfg.hd), dt),
                "v": ((batch_size, cap, cfg.n_kv_heads, cfg.hd), dt)}

    def _cache_tree(self, batch_size: int, max_len: int, make):
        cfg = self.cfg
        cap = min(cfg.window, max_len) if cfg.window else max_len
        entry = self._cache_entry(batch_size, cap)
        out = {}
        for name, _, _ in self._stacks({"dense_blocks": 0, "moe_blocks": 0}):
            n = self.n_dense if name == "dense_blocks" else self.n_moe
            out[name] = {k: make((n,) + shape, dt)
                         for k, (shape, dt) in entry.items()}
        return out

    def cache_spec(self, batch_size: int, max_len: int):
        return self._cache_tree(batch_size, max_len, jax.ShapeDtypeStruct)

    def init_cache(self, batch_size: int, max_len: int):
        return self._cache_tree(batch_size, max_len, jnp.zeros)

    # --------------------------------------------------------------- serving
    def prefill(self, params: dict, batch: dict, cache: dict):
        x, pos = self._embed_in(params, batch)
        h, cache, _ = self._run(params, x, pos, cache, None, "prefill", False)
        return logits_last(h[:, -1], self._unembed(params)), cache

    def decode_step(self, params: dict, batch: dict, cache: dict):
        cfg = self.cfg
        t = batch["t"]
        x = params["embed"][batch["token"]]
        pos = jnp.broadcast_to(t[None, None], x.shape[:2]).astype(jnp.int32)
        if cfg.rope == "learned":
            x = x + params["pos_embed"][
                jnp.clip(pos, 0, cfg.max_position - 1)]
        if cfg.rope == "mrope":
            pos = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
        h, cache, _ = self._run(params, x, pos, cache, t, "decode", False)
        return logits_last(h[:, -1], self._unembed(params)), cache
