"""Attention mixers: GQA (with sliding window / M-RoPE variants) and MLA.

Two compute paths, numerically identical:
  - `_attend_dense`: materializes the (q_len, kv_len) score matrix. Used for
    short sequences and as the oracle.
  - `_attend_chunked`: lax.scan over KV chunks with an online-softmax
    accumulator (flash-attention recurrence in pure jnp).  This is what makes
    32k/500k shapes lower with O(seq·chunk) live memory instead of O(seq^2).
    The Pallas kernel (kernels/flash_attention.py) implements the same
    recurrence with explicit VMEM tiling for real TPUs; model code dispatches
    through kernels/ops.py.

Cache layout (decode): {"k": (B, S_max, n_kv, dh), "v": ..., "idx": ()} per
layer.  Sliding-window layers allocate only `window` cache slots and write
round-robin (idx % window) — this is what bounds gemma3's long_500k memory.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.sharding import REP, constrain, mesh_axis_size
from repro.common.types import AttnConfig, ModelConfig
from repro.kernels import ops
from repro.models.layers import apply_rope, dense_init


def _kv_spec(n_kv: int):
    """KV heads shard over "model" only when they divide it; otherwise
    they are explicitly REPLICATED (production GQA-TP: each TP rank holds
    all KV heads, Q heads split).  Leaving it unconstrained lets w_k's
    column sharding leak *into* head_dim through the reshape, which turns
    the score contraction into partial-sums + a (B,T,S)-sized all-reduce
    (measured on arctic prefill: 67 TB of ICI traffic)."""
    return "model" if n_kv % mesh_axis_size("model") == 0 else REP

NEG_INF = -2.0 ** 30  # large-negative that survives bf16 round-trips

# chunk size for the online-softmax path; seqs <= this use the dense path
ATTN_CHUNK = 1024


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, a: AttnConfig, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    if a.kind == "mla":
        # deepseek-v2 multi-head latent attention
        qd = a.q_dim  # n_heads * (nope + rope)
        p = {
            "kv_down": dense_init(ks[0], (d, a.kv_lora_rank), dtype),
            "k_rope": dense_init(ks[1], (d, a.qk_rope_dim), dtype),
            # per-head up-projections from the shared latent
            "kv_up": dense_init(
                ks[2], (a.kv_lora_rank,
                        a.n_heads * (a.qk_nope_dim + a.v_head_dim)), dtype),
            "w_o": dense_init(ks[3], (a.n_heads * a.v_head_dim, d), dtype),
        }
        if a.q_lora_rank:
            p["q_down"] = dense_init(ks[4], (d, a.q_lora_rank), dtype)
            p["q_up"] = dense_init(ks[5], (a.q_lora_rank, qd), dtype)
        else:
            p["w_q"] = dense_init(ks[4], (d, qd), dtype)
        return p
    p = {
        "w_q": dense_init(ks[0], (d, a.n_heads * a.head_dim), dtype),
        "w_k": dense_init(ks[1], (d, a.n_kv_heads * a.head_dim), dtype),
        "w_v": dense_init(ks[2], (d, a.n_kv_heads * a.head_dim), dtype),
        "w_o": dense_init(ks[3], (a.n_heads * a.head_dim, d), dtype),
    }
    if a.qk_norm:
        p["norm_q"] = jnp.ones((a.head_dim,), jnp.float32)
        p["norm_k"] = jnp.ones((a.head_dim,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# core attention math (shared by dense / chunked / decode)
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, window: int, causal: bool) -> jax.Array:
    """(q, k) additive mask. window>0 limits lookback (sliding window).

    Negative k positions are the "empty / padded cache slot" sentinel and
    are always masked out.
    """
    ok = k_pos[None, :] >= 0
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _attend_dense(q, k, v, bias, scale) -> jax.Array:
    """q:(B,Tq,H,dh) k/v:(B,Tk,Hkv,dh|dv) bias:(Tq,Tk), or (B,Tq,Tk)
    for per-row masks (paged decode: every slot at its own position)
    -> (B,Tq,H,dv).

    Same precision convention as the chunked path (operands in input
    dtype, f32 MXU accumulation) so dense/chunked dispatch is a pure
    performance choice, never a numerics change.
    """
    B, Tq, H, dh = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    cdt = q.dtype
    qg = q.reshape(B, Tq, Hkv, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(cdt),
                   preferred_element_type=jnp.float32) * scale
    s = s + (bias[:, None, None] if bias.ndim == 3
             else bias[None, None, None])
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(cdt), v.astype(cdt),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Tq, H, v.shape[-1]).astype(v.dtype)


def _attend_chunked(q, k, v, q_pos, k_pos, window, causal, scale,
                    chunk: int = ATTN_CHUNK) -> jax.Array:
    """Online-softmax over KV chunks; O(Tk/chunk) sequential steps.

    KV chunks are taken with dynamic_slice per step (NOT by restacking
    (nc, B, chunk, ...) scan inputs — at decode that restack materializes
    a full transposed copy of the KV cache per step, and XLA hoists it
    over the layer loop: 2x4.3 GiB/step measured on llama3-405b).
    Memory high-water per step: the (B,Hkv,g,Tq,chunk) score tile.
    """
    B, Tq, H, dh = q.shape
    Tk = k.shape[1]
    Hkv = k.shape[2]
    g = H // Hkv
    dv = v.shape[-1]
    n_chunks = -(-Tk // chunk)
    pad = n_chunks * chunk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-(10 ** 9))
    # operands stay in the input dtype (bf16 in production) — the MXU
    # accumulates in f32 via preferred_element_type, so softmax stats are
    # exact while score/weight traffic (HBM + any collectives touching
    # them) is halved vs materializing f32 operands.
    cdt = q.dtype
    qf = q.reshape(B, Tq, Hkv, g, dh)

    @jax.checkpoint  # flash-style: recompute per-chunk scores in backward
    def step(carry, i):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(k_pos, i * chunk, chunk, axis=0)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb.astype(cdt),
                       preferred_element_type=jnp.float32) * scale
        s = s + _mask_bias(q_pos, kp, window, causal)[None, None, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(cdt), vb.astype(cdt),
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, g, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, Tq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, g, Tq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  jnp.arange(n_chunks))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, dv)
    return o.astype(v.dtype)


def attend(q, k, v, q_pos, k_pos, *, window: int, causal: bool,
           scale: float, force_dense: Optional[bool] = None) -> jax.Array:
    """Dispatch dense vs chunked on KV length."""
    Tk = k.shape[1]
    dense = Tk <= ATTN_CHUNK if force_dense is None else force_dense
    if dense:
        bias = _mask_bias(q_pos, k_pos, window, causal)
        return _attend_dense(q, k, v, bias, scale)
    return _attend_chunked(q, k, v, q_pos, k_pos, window, causal, scale)


# ---------------------------------------------------------------------------
# GQA apply (train/prefill + decode)
# ---------------------------------------------------------------------------

def _maybe_qknorm(params, q, k, eps):
    if "norm_q" in params:
        def rn(x, w):
            v = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
            return (x.astype(jnp.float32) * jax.lax.rsqrt(v + eps) * w
                    ).astype(x.dtype)
        q, k = rn(q, params["norm_q"]), rn(k, params["norm_k"])
    return q, k


def gqa_apply(params: dict, x: jax.Array, a: AttnConfig, cfg: ModelConfig,
              positions: jax.Array, window: int, theta: float,
              causal: bool = True) -> jax.Array:
    """x: (B, T, d) -> (B, T, d).  positions: (B, T) or (3, B, T) for M-RoPE."""
    B, T, _ = x.shape
    kv = _kv_spec(a.n_kv_heads)
    qs = "model" if a.n_heads % mesh_axis_size("model") == 0 else kv
    qf_ = x @ params["w_q"]
    kf = x @ params["w_k"]
    vf = x @ params["w_v"]
    if kv == REP:
        # replicate the FLAT projections before the head reshape: if the
        # column sharding survives into the reshape, shards land inside
        # head_dim and the score contraction becomes partial-sum +
        # a (B,Hkv,g,Tq,chunk)-sized all-reduce (measured: 33 TB on
        # arctic prefill).  Constraining only the head dim of the 4D view
        # is NOT enough — head_dim stays UNCONSTRAINED and keeps the
        # leaked shards (measured: the AR survived on gemma).  The
        # all-gather here is (B,T,heads*dh) — tiny by comparison.
        kf = constrain(kf, None, None, REP)
        vf = constrain(vf, None, None, REP)
    if qs == REP:
        qf_ = constrain(qf_, None, None, REP)
    q = qf_.reshape(B, T, a.n_heads, a.head_dim)
    k = kf.reshape(B, T, a.n_kv_heads, a.head_dim)
    v = vf.reshape(B, T, a.n_kv_heads, a.head_dim)
    q = constrain(q, None, None, qs, None)
    k = constrain(k, None, None, kv, None)
    v = constrain(v, None, None, kv, None)
    q, k = _maybe_qknorm(params, q, k, cfg.norm_eps)
    pos1d = positions if a.mrope_sections is None else positions[0]
    if a.use_rope:
        q = apply_rope(q, positions, theta, a.mrope_sections)
        k = apply_rope(k, positions, theta, a.mrope_sections)
    scale = 1.0 / math.sqrt(a.head_dim)
    o = attend(q, k, v, pos1d[0], pos1d[0], window=window, causal=causal,
               scale=scale)
    o = constrain(o, None, None, "model" if a.n_heads
                  % mesh_axis_size("model") == 0 else kv, None)
    return o.reshape(B, T, -1) @ params["w_o"]


def gqa_cache_init(a: AttnConfig, batch: int, max_seq: int, window: int,
                   dtype) -> dict:
    slots = min(window, max_seq) if window > 0 else max_seq
    shape = (batch, slots, a.n_kv_heads, a.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_decode(params: dict, x: jax.Array, cache: dict, idx: jax.Array,
               a: AttnConfig, cfg: ModelConfig, window: int,
               theta: float) -> Tuple[jax.Array, dict]:
    """One-token decode. x: (B, 1, d); idx: () current position."""
    B = x.shape[0]
    kv = _kv_spec(a.n_kv_heads)
    kf, vf = x @ params["w_k"], x @ params["w_v"]
    if kv == REP:  # see gqa_apply: keep shards out of head_dim
        kf = constrain(kf, None, None, REP)
        vf = constrain(vf, None, None, REP)
    q = (x @ params["w_q"]).reshape(B, 1, a.n_heads, a.head_dim)
    k = kf.reshape(B, 1, a.n_kv_heads, a.head_dim)
    v = vf.reshape(B, 1, a.n_kv_heads, a.head_dim)
    q, k = _maybe_qknorm(params, q, k, cfg.norm_eps)
    pos = jnp.full((B, 1), idx, jnp.int32)
    if a.mrope_sections is not None:
        pos3 = jnp.broadcast_to(pos, (3,) + pos.shape)
        if a.use_rope:
            q = apply_rope(q, pos3, theta, a.mrope_sections)
            k = apply_rope(k, pos3, theta, a.mrope_sections)
    elif a.use_rope:
        q = apply_rope(q, pos, theta)
        k = apply_rope(k, pos, theta)
    slots = cache["k"].shape[1]
    slot = idx % slots if window > 0 else idx
    k = constrain(k, None, None, kv, None)
    v = constrain(v, None, None, kv, None)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    # absolute positions of cache slots (round-robin for windows)
    slot_ids = jnp.arange(slots)
    if window > 0:
        # slot s holds the most recent position p <= idx with p % slots == s
        k_pos = idx - ((idx - slot_ids) % slots)
        k_pos = jnp.where(k_pos > idx, -(10 ** 9), k_pos)
    else:
        k_pos = jnp.where(slot_ids <= idx, slot_ids, -(10 ** 9))
    scale = 1.0 / math.sqrt(a.head_dim)
    o = attend(q, ck, cv, pos[0], k_pos, window=window, causal=True,
               scale=scale, force_dense=slots <= ATTN_CHUNK * 4)
    o = o.reshape(B, 1, -1) @ params["w_o"]
    return o, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# GQA chunk prefill
# ---------------------------------------------------------------------------

def chunk_cache_write(plane: jax.Array, chunk: jax.Array, idx: jax.Array,
                      n_tok: jax.Array, window: int) -> jax.Array:
    """Bulk-write a prompt chunk into a positional cache plane.

    plane: (B, S, ...) cache; chunk: (B, C, ...) entries for positions
    idx..idx+n_tok-1 (t >= n_tok is padding and is NOT written).  For
    sliding-window caches the slot for position p is p % S and a chunk
    longer than the ring keeps only the last S positions — the write is
    a single deterministic scatter (losers map to the dropped
    out-of-range index), never a duplicate-index race.  n_tok == 0 is a
    bit-exact no-op.
    """
    S, C = plane.shape[1], chunk.shape[1]
    t = jnp.arange(C)
    if window > 0:
        tgt = (idx + t) % S
        win = (t < n_tok) & (t >= n_tok - S)  # ring: last S positions win
    else:
        tgt = idx + t
        win = t < n_tok
    tgt = jnp.where(win, tgt, S)  # S is out of range -> dropped
    return plane.at[:, tgt].set(chunk, mode="drop")


def _chunk_q_pos(idx: jax.Array, B: int, C: int, mrope: bool):
    pos = jnp.broadcast_to(idx + jnp.arange(C, dtype=jnp.int32), (B, C))
    return jnp.broadcast_to(pos, (3, B, C)) if mrope else pos


def _cache_entry_pos(slots: int, idx: jax.Array, window: int) -> jax.Array:
    """Absolute positions held by cache slots BEFORE a chunk at `idx` is
    written (positions < idx); empty/future slots get the mask sentinel."""
    slot_ids = jnp.arange(slots)
    last = idx - 1
    if window > 0:
        # slot s holds the most recent p <= last with p % slots == s
        pos = last - ((last - slot_ids) % slots)
    else:
        pos = slot_ids
    return jnp.where((pos >= 0) & (pos <= last), pos, -(10 ** 9))


def gqa_prefill(params: dict, x: jax.Array, cache: dict, idx: jax.Array,
                n_tok: jax.Array, a: AttnConfig, cfg: ModelConfig,
                window: int, theta: float) -> Tuple[jax.Array, dict]:
    """Multi-token prefill. x: (B, C, d) chunk at positions idx..idx+C-1;
    n_tok () valid tokens (the tail is padding: masked out of attention
    and never written).  Queries attend causally over the pre-existing
    cache entries plus the chunk itself, then the chunk's K/V land in
    the cache in one bulk write.  -> (out (B, C, d), cache)."""
    B, C, _ = x.shape
    kv = _kv_spec(a.n_kv_heads)
    kf, vf = x @ params["w_k"], x @ params["w_v"]
    if kv == REP:  # see gqa_apply: keep shards out of head_dim
        kf = constrain(kf, None, None, REP)
        vf = constrain(vf, None, None, REP)
    q = (x @ params["w_q"]).reshape(B, C, a.n_heads, a.head_dim)
    k = kf.reshape(B, C, a.n_kv_heads, a.head_dim)
    v = vf.reshape(B, C, a.n_kv_heads, a.head_dim)
    q, k = _maybe_qknorm(params, q, k, cfg.norm_eps)
    pos = _chunk_q_pos(idx, B, C, a.mrope_sections is not None)
    if a.use_rope:
        q = apply_rope(q, pos, theta, a.mrope_sections)
        k = apply_rope(k, pos, theta, a.mrope_sections)
    k = constrain(k, None, None, kv, None)
    v = constrain(v, None, None, kv, None)
    slots = cache["k"].shape[1]
    pos1d = pos if a.mrope_sections is None else pos[0]
    t = jnp.arange(C)
    chunk_pos = jnp.where(t < n_tok, idx + t, -(10 ** 9))
    k_pos = jnp.concatenate([_cache_entry_pos(slots, idx, window),
                             chunk_pos])
    k_all = jnp.concatenate([cache["k"], k], axis=1)
    v_all = jnp.concatenate([cache["v"], v], axis=1)
    scale = 1.0 / math.sqrt(a.head_dim)
    o = attend(q, k_all, v_all, pos1d[0], k_pos, window=window, causal=True,
               scale=scale, force_dense=(slots + C) <= ATTN_CHUNK * 4)
    o = o.reshape(B, C, -1) @ params["w_o"]
    ck = chunk_cache_write(cache["k"], k, idx, n_tok, window)
    cv = chunk_cache_write(cache["v"], v, idx, n_tok, window)
    return o, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (deepseek-v2): latent-compressed KV
# ---------------------------------------------------------------------------

def _mla_qkv(params, x, a: AttnConfig):
    B, T, _ = x.shape
    if "q_down" in params:
        q = (x @ params["q_down"]) @ params["q_up"]
    else:
        q = x @ params["w_q"]
    q = q.reshape(B, T, a.n_heads, a.qk_nope_dim + a.qk_rope_dim)
    c_kv = x @ params["kv_down"]            # (B, T, r) latent
    k_r = x @ params["k_rope"]              # (B, T, rope_dim) shared rope key
    return q, c_kv, k_r


def _mla_expand(params, c_kv, a: AttnConfig):
    B, T, _ = c_kv.shape
    kv = (c_kv @ params["kv_up"]).reshape(
        B, T, a.n_heads, a.qk_nope_dim + a.v_head_dim)
    k_c, v = kv[..., :a.qk_nope_dim], kv[..., a.qk_nope_dim:]
    return k_c, v


def mla_absorbed(params: dict, a: AttnConfig) -> Tuple[jax.Array, jax.Array]:
    """(W_UK (r, H, nope), W_UV (r, H, v)) — kv_up split for the
    absorbed decode form.

    Instead of expanding every cached latent to per-head K/V
    (`_mla_expand`, O(S) work per decode step), W_UK folds into the
    query (q_lat[b,h] = q_nope[b,h] @ W_UK[:,h,:]^T, so scores are
    q_lat · c_kv — the latent IS the key) and W_UV folds into the
    output (o[b,h] = o_lat[b,h] @ W_UV[:,h,:], the latent IS the
    value).  Same linear algebra, contraction order swapped.  Prefers
    the precomputed leaves a serving engine installs once per
    swap_params (transformer.absorb_mla_params); the reshape fallback
    keeps the function usable on raw trees.
    """
    if "kv_uk" in params:
        return params["kv_uk"], params["kv_uv"]
    w = params["kv_up"].reshape(-1, a.n_heads, a.qk_nope_dim + a.v_head_dim)
    return w[..., :a.qk_nope_dim], w[..., a.qk_nope_dim:]


def mla_apply(params: dict, x: jax.Array, a: AttnConfig, cfg: ModelConfig,
              positions: jax.Array, theta: float) -> jax.Array:
    B, T, _ = x.shape
    q, c_kv, k_r = _mla_qkv(params, x, a)
    q_c, q_r = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_r = apply_rope(q_r, positions, theta)
    k_r = apply_rope(k_r[..., None, :], positions, theta)  # (B,T,1,rope)
    k_c, v = _mla_expand(params, c_kv, a)
    q_full = jnp.concatenate([q_c, q_r], -1)
    k_full = jnp.concatenate(
        [k_c, jnp.broadcast_to(k_r, k_c.shape[:-1] + (a.qk_rope_dim,))], -1)
    q_full = constrain(q_full, None, None, "model", None)
    k_full = constrain(k_full, None, None, "model", None)
    scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    o = attend(q_full, k_full, v, positions[0], positions[0], window=0,
               causal=True, scale=scale)
    o = constrain(o, None, None, "model", None)
    return o.reshape(B, T, -1) @ params["w_o"]


def mla_cache_init(a: AttnConfig, batch: int, max_seq: int, dtype) -> dict:
    # cache the *latent* (this is MLA's point: r + rope_dim per token,
    # not n_heads*dh) — 512+64 vs 128*192 for deepseek-v2.
    return {"c_kv": jnp.zeros((batch, max_seq, a.kv_lora_rank), dtype),
            "k_r": jnp.zeros((batch, max_seq, a.qk_rope_dim), dtype)}


def mla_decode(params: dict, x: jax.Array, cache: dict, idx: jax.Array,
               a: AttnConfig, cfg: ModelConfig,
               theta: float) -> Tuple[jax.Array, dict]:
    B = x.shape[0]
    q, c_kv, k_r = _mla_qkv(params, x, a)
    pos = jnp.full((B, 1), idx, jnp.int32)
    q_c, q_r = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_r = apply_rope(q_r, pos, theta)
    k_r = apply_rope(k_r[..., None, :], pos, theta)[..., 0, :]
    cc = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv, idx, 1)
    cr = jax.lax.dynamic_update_slice_in_dim(cache["k_r"], k_r, idx, 1)
    S = cc.shape[1]
    k_c, v = _mla_expand(params, cc, a)  # (B,S,H,*) expanded on the fly
    k_pos = jnp.where(jnp.arange(S) <= idx, jnp.arange(S), -(10 ** 9))
    q_full = jnp.concatenate([q_c, q_r], -1)
    k_full = jnp.concatenate(
        [k_c, jnp.broadcast_to(cr[..., None, :],
                               k_c.shape[:-1] + (a.qk_rope_dim,))], -1)
    scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    o = attend(q_full, k_full, v, pos[0], k_pos, window=0, causal=True,
               scale=scale)
    o = o.reshape(B, 1, -1) @ params["w_o"]
    return o, {"c_kv": cc, "k_r": cr}


def mla_prefill(params: dict, x: jax.Array, cache: dict, idx: jax.Array,
                n_tok: jax.Array, a: AttnConfig, cfg: ModelConfig,
                theta: float) -> Tuple[jax.Array, dict]:
    """Multi-token MLA prefill: bulk-write the chunk's latents, then
    attend over the expanded cache (entries past idx+n_tok stay masked,
    exactly as in mla_decode).  -> (out (B, C, d), cache)."""
    B, C, _ = x.shape
    q, c_kv, k_r = _mla_qkv(params, x, a)
    pos = _chunk_q_pos(idx, B, C, False)
    q_c, q_r = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_r = apply_rope(q_r, pos, theta)
    k_r = apply_rope(k_r[..., None, :], pos, theta)[..., 0, :]
    cc = chunk_cache_write(cache["c_kv"], c_kv, idx, n_tok, 0)
    cr = chunk_cache_write(cache["k_r"], k_r, idx, n_tok, 0)
    S = cc.shape[1]
    k_c, v = _mla_expand(params, cc, a)
    slot_ids = jnp.arange(S)
    k_pos = jnp.where(slot_ids < idx + n_tok, slot_ids, -(10 ** 9))
    q_full = jnp.concatenate([q_c, q_r], -1)
    k_full = jnp.concatenate(
        [k_c, jnp.broadcast_to(cr[..., None, :],
                               k_c.shape[:-1] + (a.qk_rope_dim,))], -1)
    scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    o = attend(q_full, k_full, v, pos[0], k_pos, window=0, causal=True,
               scale=scale)
    o = o.reshape(B, C, -1) @ params["w_o"]
    return o, {"c_kv": cc, "k_r": cr}


# ---------------------------------------------------------------------------
# paged KV cache (serving): fixed-size pages + per-slot page table
# ---------------------------------------------------------------------------
# The serving engine's paged pool (serving/kv_cache.py) replaces the
# per-slot contiguous (B, max_seq, ...) planes of FULL-attention layers
# with a shared (n_pages, page_size, ...) pool addressed through a
# per-slot page table: logical position p of slot b lives at
# (table[b, p // page_size], p % page_size).  Sliding-window layers keep
# their contiguous rings — they are already O(window), paging buys them
# nothing.  Unallocated table entries carry a sentinel >= n_pages:
# writes drop (scatter mode="drop"), reads clamp and are masked by the
# position bookkeeping — the same stale-entry invariant the contiguous
# pool relies on.


# -- quantized pages --------------------------------------------------------
# kv_dtype selects the STORAGE format of paged planes only ("f32" = the
# model's native dtype, today's layout, bit-identical).  int8/fp8 planes
# carry a per-token, per-kv-head absmax scale in a sidecar plane named
# `<plane>_scale_pages` with the page axes leading — the "_pages" suffix
# means every pool helper (reset/slot_row/copy_pages/snapshot) already
# treats a sidecar exactly like its plane, and per-token granularity
# makes single-token scatter writes rescale-free: a write never has to
# requantize its page neighbors.  Sliding-window rings and recurrent
# state are NOT quantized (they are already O(window)/O(1) and live
# outside the paged pool).

KV_DTYPES = ("f32", "bf16", "int8", "fp8")
_INT8_MAX = 127.0
_FP8_MAX = 448.0  # float8_e4m3fn finite max


def kv_quantized(kv_dtype: str) -> bool:
    return kv_dtype in ("int8", "fp8")


def kv_storage_dtype(kv_dtype: str, dtype):
    """Storage dtype of a paged K/V plane under `kv_dtype` ('f32' keeps
    the model's native dtype)."""
    if kv_dtype == "f32":
        return dtype
    if kv_dtype == "bf16":
        return jnp.bfloat16
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                     f"got {kv_dtype!r}")


def kv_quantize(vals: jax.Array, qdtype) -> Tuple[jax.Array, jax.Array]:
    """(..., d) -> ((..., d) qdtype, (...,) f32 absmax scale).

    scale = absmax(vals)/Q over the trailing feature axis, one scale per
    token (and per kv head, since the head axis precedes the feature
    axis in every paged plane).  An all-zero vector quantizes to zeros
    with scale 0 — dequant reproduces the zeros exactly.
    """
    v = vals.astype(jnp.float32)
    qmax = _INT8_MAX if jnp.issubdtype(qdtype, jnp.integer) else _FP8_MAX
    scale = jnp.max(jnp.abs(v), axis=-1) / qmax
    q = v / jnp.maximum(scale, 1e-30)[..., None]
    if jnp.issubdtype(qdtype, jnp.integer):
        q = jnp.round(q).clip(-_INT8_MAX, _INT8_MAX)
    return q.astype(qdtype), scale


def kv_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of kv_quantize: f32 values (math stays f32 in-register)."""
    return q.astype(jnp.float32) * scale[..., None]


def _scale_name(name: str) -> str:
    return name[: -len("_pages")] + "_scale_pages"


def gqa_paged_cache_init(a: AttnConfig, n_pages: int, page_size: int,
                         dtype, kv_dtype: str = "f32") -> dict:
    sdtype = kv_storage_dtype(kv_dtype, dtype)
    shape = (n_pages, page_size, a.n_kv_heads, a.head_dim)
    c = {"k_pages": jnp.zeros(shape, sdtype),
         "v_pages": jnp.zeros(shape, sdtype)}
    if kv_quantized(kv_dtype):
        ss = (n_pages, page_size, a.n_kv_heads)
        c["k_scale_pages"] = jnp.zeros(ss, jnp.float32)
        c["v_scale_pages"] = jnp.zeros(ss, jnp.float32)
    return c


def mla_paged_cache_init(a: AttnConfig, n_pages: int, page_size: int,
                         dtype, kv_dtype: str = "f32") -> dict:
    # pages hold the latent (MLA's point: r + rope_dim per token).  The
    # rope keys stay in the native dtype under int8/fp8: they are
    # rope_dim/kv_lora_rank of the bytes and feed the kernel as the
    # unquantized `k_extra` feature block, so the dominant latent plane
    # quantizes without a second scale family.
    sdtype = kv_storage_dtype(kv_dtype, dtype)
    rdtype = dtype if kv_quantized(kv_dtype) else sdtype
    c = {"c_kv_pages": jnp.zeros((n_pages, page_size, a.kv_lora_rank),
                                 sdtype),
         "k_r_pages": jnp.zeros((n_pages, page_size, a.qk_rope_dim),
                                rdtype)}
    if kv_quantized(kv_dtype):
        c["c_kv_scale_pages"] = jnp.zeros((n_pages, page_size),
                                          jnp.float32)
    return c


def _scatter_token(plane: jax.Array, vals: jax.Array, table: jax.Array,
                   pos: jax.Array) -> jax.Array:
    """Write one token per slot into a paged plane.

    plane: (n_pages, page, ...); vals: (B, ...); table: (B, P);
    pos: (B,) logical positions.  Slots whose target page is
    unallocated (sentinel) drop the write — the engine only lets rows
    with allocated pages advance, so a dropped write is always a frozen
    slot's garbage step (same invariant as kv_cache.keep_frozen).
    """
    n_pages, page = plane.shape[0], plane.shape[1]
    P = table.shape[1]
    l = pos // page
    off = pos % page
    phys = jnp.take_along_axis(table, jnp.clip(l, 0, P - 1)[:, None],
                               axis=1)[:, 0]
    phys = jnp.where(l < P, phys, n_pages)  # out-of-table -> drop
    # distinct slots own distinct pages (allocator invariant), so the
    # scatter indices never collide on valid rows
    return plane.at[phys, off].set(vals, mode="drop")


def _gather_pages(plane: jax.Array, table: jax.Array) -> jax.Array:
    """(n_pages, page, ...) x (B?, P) -> (B?, P*page, ...) logical view.
    Unallocated entries clamp to an arbitrary live page; callers mask
    them by position."""
    n_pages, page = plane.shape[0], plane.shape[1]
    t = jnp.clip(table, 0, n_pages - 1)
    out = plane[t]
    lead = table.shape[:-1]
    return out.reshape(lead + (table.shape[-1] * page,) + plane.shape[2:])


# -- quantize-on-write / dequantize-on-read wrappers ------------------------
# Every paged write/read goes through these: when the layer's cache
# carries a `<plane>_scale_pages` sidecar the values are quantized on
# the way in (one absmax scale per token written) and dequantized to
# f32 on the way out; otherwise the plane's dtype is a plain cast
# (no-op for kv_dtype='f32', preserving bit-identity with the
# unquantized layout).  Each wrapper returns the dict of UPDATED leaves
# so callers can merge plane + sidecar updates in one place.


def paged_write_token(cache: dict, name: str, vals: jax.Array,
                      table: jax.Array, pos: jax.Array) -> dict:
    plane = cache[name]
    sname = _scale_name(name)
    if sname in cache:
        q, s = kv_quantize(vals, plane.dtype)
        return {name: _scatter_token(plane, q, table, pos),
                sname: _scatter_token(cache[sname], s, table, pos)}
    return {name: _scatter_token(plane, vals.astype(plane.dtype), table,
                                 pos)}


def paged_write_chunk(cache: dict, name: str, chunk: jax.Array,
                      table: jax.Array, idx: jax.Array,
                      n_tok: jax.Array) -> dict:
    plane = cache[name]
    sname = _scale_name(name)
    if sname in cache:
        q, s = kv_quantize(chunk, plane.dtype)
        return {name: chunk_cache_write_paged(plane, q, table, idx, n_tok),
                sname: chunk_cache_write_paged(cache[sname], s, table, idx,
                                               n_tok)}
    return {name: chunk_cache_write_paged(plane, chunk.astype(plane.dtype),
                                          table, idx, n_tok)}


def paged_write_batch(cache: dict, name: str, chunk: jax.Array,
                      table: jax.Array, pos: jax.Array,
                      n_tok: jax.Array) -> dict:
    plane = cache[name]
    sname = _scale_name(name)
    if sname in cache:
        q, s = kv_quantize(chunk, plane.dtype)
        return {name: chunk_scatter_batch(plane, q, table, pos, n_tok),
                sname: chunk_scatter_batch(cache[sname], s, table, pos,
                                           n_tok)}
    return {name: chunk_scatter_batch(plane, chunk.astype(plane.dtype),
                                      table, pos, n_tok)}


def paged_gather(cache: dict, name: str, table: jax.Array,
                 out_dtype=None) -> jax.Array:
    """_gather_pages + dequantization for the dense (prefill/verify)
    read paths.  out_dtype casts the logical view to the compute dtype
    (no-op when the plane already stores it, i.e. kv_dtype='f32')."""
    out = _gather_pages(cache[name], table)
    sname = _scale_name(name)
    if sname in cache:
        out = kv_dequantize(out, _gather_pages(cache[sname], table))
    if out_dtype is not None and out.dtype != out_dtype:
        out = out.astype(out_dtype)
    return out


def chunk_cache_write_paged(plane: jax.Array, chunk: jax.Array,
                            table: jax.Array, idx: jax.Array,
                            n_tok: jax.Array) -> jax.Array:
    """Bulk-write a prompt chunk into a paged plane (one slot).

    plane: (n_pages, page, ...); chunk: (C, ...) entries for positions
    idx..idx+n_tok-1 (t >= n_tok is padding and is NOT written);
    table: (P,) the slot's page-table row.  The paged twin of
    chunk_cache_write — same deterministic single-scatter contract,
    n_tok == 0 is a bit-exact no-op.  No ring arithmetic: paged layers
    are full-attention (window 0 or >= max_seq), so positions never
    wrap inside max_seq.

    Writes land ONLY at positions idx..idx+n_tok-1 — pages below idx
    are read, never written.  Prefix caching (serving/prefix.py) leans
    on exactly that: a prefix-hit slot's chain starts with SHARED
    pages other requests also read, and admission sets idx to the hit
    boundary, so this scatter can never touch them (the partial
    boundary page is copy-on-write-swapped for a private copy before
    the chunk dispatches).
    """
    n_pages, page = plane.shape[0], plane.shape[1]
    P = table.shape[0]
    C = chunk.shape[0]
    t = jnp.arange(C)
    pos = idx + t
    l = pos // page
    off = pos % page
    phys = table[jnp.clip(l, 0, P - 1)]
    phys = jnp.where((t < n_tok) & (l < P), phys, n_pages)  # pad -> drop
    return plane.at[phys, off].set(chunk, mode="drop")


def _rows_bias(lens: jax.Array, S: int, window: int) -> jax.Array:
    """(B, 1, S) additive mask for per-row decode: entries < lens valid,
    window limits lookback from the query position lens-1."""
    kp = jnp.arange(S)
    ok = kp[None, :] < lens[:, None]
    if window > 0:
        ok &= kp[None, :] > (lens[:, None] - 1 - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[:, None]


def gqa_decode_paged(params: dict, x: jax.Array, cache: dict,
                     pos: jax.Array, table: jax.Array, a: AttnConfig,
                     cfg: ModelConfig, window: int,
                     theta: float) -> Tuple[jax.Array, dict]:
    """One-token decode over a paged pool, every row at its OWN position.

    x: (B, 1, d); pos: (B,) per-row positions; table: (B, P) page table;
    cache: {"k_pages": (n_pages, page, n_kv, dh), "v_pages": ...}.  The
    new token's KV scatters into the slot's current page, then attention
    reads the slot's pages through kernels/ops.paged_attention (Pallas
    O(len) kernel on TPU, gather reference elsewhere).
    """
    B = x.shape[0]
    kv = _kv_spec(a.n_kv_heads)
    kf, vf = x @ params["w_k"], x @ params["w_v"]
    if kv == REP:  # see gqa_apply: keep shards out of head_dim
        kf = constrain(kf, None, None, REP)
        vf = constrain(vf, None, None, REP)
    q = (x @ params["w_q"]).reshape(B, 1, a.n_heads, a.head_dim)
    k = kf.reshape(B, 1, a.n_kv_heads, a.head_dim)
    v = vf.reshape(B, 1, a.n_kv_heads, a.head_dim)
    q, k = _maybe_qknorm(params, q, k, cfg.norm_eps)
    pos2 = pos[:, None]  # (B, 1) per-row, vs gqa_decode's shared scalar
    if a.mrope_sections is not None:
        pos3 = jnp.broadcast_to(pos2, (3,) + pos2.shape)
        if a.use_rope:
            q = apply_rope(q, pos3, theta, a.mrope_sections)
            k = apply_rope(k, pos3, theta, a.mrope_sections)
    elif a.use_rope:
        q = apply_rope(q, pos2, theta)
        k = apply_rope(k, pos2, theta)
    k = constrain(k, None, None, kv, None)
    v = constrain(v, None, None, kv, None)
    upd = paged_write_token(cache, "k_pages", k[:, 0], table, pos)
    upd.update(paged_write_token(cache, "v_pages", v[:, 0], table, pos))
    scale = 1.0 / math.sqrt(a.head_dim)
    o = ops.paged_attention(q[:, 0], upd["k_pages"], upd["v_pages"],
                            table, pos + 1, window=window, scale=scale,
                            k_scale=upd.get("k_scale_pages"),
                            v_scale=upd.get("v_scale_pages"))
    o = o.reshape(B, 1, -1) @ params["w_o"]
    return o, upd


def gqa_prefill_paged(params: dict, x: jax.Array, cache: dict,
                      idx: jax.Array, n_tok: jax.Array, table: jax.Array,
                      a: AttnConfig, cfg: ModelConfig, window: int,
                      theta: float) -> Tuple[jax.Array, dict]:
    """Multi-token prefill of ONE slot over a paged pool.

    x: (1, C, d) chunk at positions idx..idx+C-1; table: (P,) the slot's
    page-table row.  Same math as gqa_prefill — queries attend over the
    gathered pre-existing pages plus the chunk, then the chunk's K/V
    land in the slot's pages in one scatter.
    """
    B, C, _ = x.shape
    kv = _kv_spec(a.n_kv_heads)
    kf, vf = x @ params["w_k"], x @ params["w_v"]
    if kv == REP:
        kf = constrain(kf, None, None, REP)
        vf = constrain(vf, None, None, REP)
    q = (x @ params["w_q"]).reshape(B, C, a.n_heads, a.head_dim)
    k = kf.reshape(B, C, a.n_kv_heads, a.head_dim)
    v = vf.reshape(B, C, a.n_kv_heads, a.head_dim)
    q, k = _maybe_qknorm(params, q, k, cfg.norm_eps)
    pos = _chunk_q_pos(idx, B, C, a.mrope_sections is not None)
    if a.use_rope:
        q = apply_rope(q, pos, theta, a.mrope_sections)
        k = apply_rope(k, pos, theta, a.mrope_sections)
    k = constrain(k, None, None, kv, None)
    v = constrain(v, None, None, kv, None)
    k_cache = paged_gather(cache, "k_pages", table[None],
                           k.dtype)            # (1, S, kv, dh)
    v_cache = paged_gather(cache, "v_pages", table[None], v.dtype)
    S = k_cache.shape[1]
    pos1d = pos if a.mrope_sections is None else pos[0]
    t = jnp.arange(C)
    chunk_pos = jnp.where(t < n_tok, idx + t, -(10 ** 9))
    slot_ids = jnp.arange(S)
    cache_pos = jnp.where(slot_ids < idx, slot_ids, -(10 ** 9))
    k_pos = jnp.concatenate([cache_pos, chunk_pos])
    k_all = jnp.concatenate([k_cache, k], axis=1)
    v_all = jnp.concatenate([v_cache, v], axis=1)
    scale = 1.0 / math.sqrt(a.head_dim)
    o = attend(q, k_all, v_all, pos1d[0], k_pos, window=window, causal=True,
               scale=scale, force_dense=(S + C) <= ATTN_CHUNK * 4)
    o = o.reshape(B, C, -1) @ params["w_o"]
    upd = paged_write_chunk(cache, "k_pages", k[0], table, idx, n_tok)
    upd.update(paged_write_chunk(cache, "v_pages", v[0], table, idx, n_tok))
    return o, upd


def mla_decode_paged(params: dict, x: jax.Array, cache: dict,
                     pos: jax.Array, table: jax.Array, a: AttnConfig,
                     cfg: ModelConfig,
                     theta: float) -> Tuple[jax.Array, dict]:
    """MLA one-token decode over paged LATENT planes in the ABSORBED
    projection form, per-row positions.

    The pages hold the compressed latent (c_kv, k_r); the step scatters
    the new token's latent and feeds the latent pages to
    ops.paged_attention DIRECTLY: W_UK is folded into the queries and
    W_UV into the output (mla_absorbed), so attention runs at
    dk = kv_lora_rank + rope_dim / dv = kv_lora_rank with the rope keys
    as the kernel's unquantized `k_extra` block — no `_mla_expand` of
    the whole gathered sequence on the hot path.  Per-step work is
    O(1) in max_seq (plus the kernel's O(len) page walk); greedy output
    is token-exact vs the expanded path at f32 (same linear algebra,
    reassociated contractions).
    """
    B = x.shape[0]
    q, c_kv, k_r = _mla_qkv(params, x, a)
    pos2 = pos[:, None]
    q_c, q_r = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_r = apply_rope(q_r, pos2, theta)
    k_r = apply_rope(k_r[..., None, :], pos2, theta)[..., 0, :]
    upd = paged_write_token(cache, "c_kv_pages", c_kv[:, 0], table, pos)
    upd.update(paged_write_token(cache, "k_r_pages", k_r[:, 0], table, pos))
    w_uk, w_uv = mla_absorbed(params, a)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_c[:, 0], w_uk)
    q_abs = jnp.concatenate([q_lat, q_r[:, 0]], -1)  # (B, H, r + rope)
    scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    c_scale = upd.get("c_kv_scale_pages")
    o_lat = ops.paged_attention(
        q_abs, upd["c_kv_pages"][:, :, None], upd["c_kv_pages"][:, :, None],
        table, pos + 1, scale=scale,
        k_scale=None if c_scale is None else c_scale[:, :, None],
        v_scale=None if c_scale is None else c_scale[:, :, None],
        k_extra=upd["k_r_pages"][:, :, None])  # (B, H, r)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
    o = o.reshape(B, 1, -1) @ params["w_o"]
    return o, upd


def mla_prefill_paged(params: dict, x: jax.Array, cache: dict,
                      idx: jax.Array, n_tok: jax.Array, table: jax.Array,
                      a: AttnConfig, cfg: ModelConfig,
                      theta: float) -> Tuple[jax.Array, dict]:
    """Multi-token MLA prefill of ONE slot over paged latent planes:
    scatter the chunk's latents, gather + expand, attend with entries
    past idx+n_tok masked — the paged twin of mla_prefill."""
    B, C, _ = x.shape
    q, c_kv, k_r = _mla_qkv(params, x, a)
    pos = _chunk_q_pos(idx, B, C, False)
    q_c, q_r = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_r = apply_rope(q_r, pos, theta)
    k_r = apply_rope(k_r[..., None, :], pos, theta)[..., 0, :]
    upd = paged_write_chunk(cache, "c_kv_pages", c_kv[0], table, idx,
                            n_tok)
    upd.update(paged_write_chunk(cache, "k_r_pages", k_r[0], table, idx,
                                 n_tok))
    c2 = dict(cache)
    c2.update(upd)
    lat = paged_gather(c2, "c_kv_pages", table[None], c_kv.dtype)  # (1,S,r)
    rop = paged_gather(c2, "k_r_pages", table[None], k_r.dtype)
    S = lat.shape[1]
    k_c, v = _mla_expand(params, lat, a)
    slot_ids = jnp.arange(S)
    k_pos = jnp.where(slot_ids < idx + n_tok, slot_ids, -(10 ** 9))
    q_full = jnp.concatenate([q_c, q_r], -1)
    k_full = jnp.concatenate(
        [k_c, jnp.broadcast_to(rop[..., None, :],
                               k_c.shape[:-1] + (a.qk_rope_dim,))], -1)
    scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    o = attend(q_full, k_full, v, pos[0], k_pos, window=0, causal=True,
               scale=scale)
    o = o.reshape(B, C, -1) @ params["w_o"]
    return o, upd


# ---------------------------------------------------------------------------
# paged speculative verify: batched multi-token scoring at per-row positions
# ---------------------------------------------------------------------------
# Speculative decoding scores a (gamma+1)-token draft chunk for EVERY
# slot in one call.  The per-slot prefill entry points above handle one
# slot at a time (their tables are (P,)), and the row-vmap trick cannot
# carry the shared paged planes, so these batched siblings scatter the
# whole batch's chunks through (B, P) tables and attend densely with a
# per-row (B, C, S) bias.  Full-attention only (the paged invariant):
# positions never wrap, so stale entries past each row's position are
# masked by causality alone.


def chunk_scatter_batch(plane: jax.Array, chunk: jax.Array,
                        table: jax.Array, pos: jax.Array,
                        n_tok: jax.Array) -> jax.Array:
    """Bulk-write per-slot chunks into a paged plane, ALL slots at once.

    plane: (n_pages, page, ...); chunk: (B, C, ...) entries for row b's
    positions pos[b]..pos[b]+n_tok[b]-1 (the tail is padding and is NOT
    written); table: (B, P); pos/n_tok: (B,).  The batched twin of
    chunk_cache_write_paged: out-of-table or padded targets map to the
    dropped sentinel, distinct slots own distinct pages (allocator
    invariant), so the scatter never races.  n_tok[b] == 0 rows are
    bit-exact no-ops.
    """
    n_pages, page = plane.shape[0], plane.shape[1]
    P = table.shape[1]
    C = chunk.shape[1]
    t = jnp.arange(C)[None, :]
    p = pos[:, None] + t                    # (B, C) logical positions
    l = p // page
    off = p % page
    phys = jnp.take_along_axis(table, jnp.clip(l, 0, P - 1), axis=1)
    phys = jnp.where((t < n_tok[:, None]) & (l < P), phys, n_pages)
    return plane.at[phys, off].set(chunk, mode="drop")


def _verify_bias(pos: jax.Array, S: int, C: int, window: int) -> jax.Array:
    """(B, C, S) additive mask for batched chunk verify: row b's query i
    sits at position pos[b]+i and sees cache entries at positions
    <= pos[b]+i (stale/padded entries live past that, so causality masks
    them); window > 0 limits lookback."""
    q_pos = pos[:, None] + jnp.arange(C)[None, :]       # (B, C)
    kp = jnp.arange(S)[None, None, :]
    ok = kp <= q_pos[:, :, None]
    if window > 0:
        ok &= kp > q_pos[:, :, None] - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def gqa_verify_paged(params: dict, x: jax.Array, cache: dict,
                     pos: jax.Array, n_tok: jax.Array, table: jax.Array,
                     a: AttnConfig, cfg: ModelConfig, window: int,
                     theta: float) -> Tuple[jax.Array, dict]:
    """Score a C-token chunk for every slot over a paged pool.

    x: (B, C, d) draft chunks at positions pos..pos+C-1; n_tok: (B,)
    valid tokens per row (0 freezes the row bit-exactly); table: (B, P).
    Scatter-then-gather: the chunk's K/V land in each slot's pages
    first, then every query attends over the gathered logical view with
    a per-row causal bias — same math as gqa_prefill_paged, batched.
    -> (out (B, C, d), cache).
    """
    B, C, _ = x.shape
    kv = _kv_spec(a.n_kv_heads)
    kf, vf = x @ params["w_k"], x @ params["w_v"]
    if kv == REP:  # see gqa_apply: keep shards out of head_dim
        kf = constrain(kf, None, None, REP)
        vf = constrain(vf, None, None, REP)
    q = (x @ params["w_q"]).reshape(B, C, a.n_heads, a.head_dim)
    k = kf.reshape(B, C, a.n_kv_heads, a.head_dim)
    v = vf.reshape(B, C, a.n_kv_heads, a.head_dim)
    q, k = _maybe_qknorm(params, q, k, cfg.norm_eps)
    p2 = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (B, C)
    rp = (jnp.broadcast_to(p2, (3,) + p2.shape)
          if a.mrope_sections is not None else p2)
    if a.use_rope:
        q = apply_rope(q, rp, theta, a.mrope_sections)
        k = apply_rope(k, rp, theta, a.mrope_sections)
    k = constrain(k, None, None, kv, None)
    v = constrain(v, None, None, kv, None)
    upd = paged_write_batch(cache, "k_pages", k, table, pos, n_tok)
    upd.update(paged_write_batch(cache, "v_pages", v, table, pos, n_tok))
    c2 = dict(cache)
    c2.update(upd)
    kk = paged_gather(c2, "k_pages", table, k.dtype)  # (B, S, n_kv, dh)
    vv = paged_gather(c2, "v_pages", table, v.dtype)
    scale = 1.0 / math.sqrt(a.head_dim)
    o = _attend_dense(q, kk, vv, _verify_bias(pos, kk.shape[1], C, window),
                      scale)
    o = o.reshape(B, C, -1) @ params["w_o"]
    return o, upd


def mla_verify_paged(params: dict, x: jax.Array, cache: dict,
                     pos: jax.Array, n_tok: jax.Array, table: jax.Array,
                     a: AttnConfig, cfg: ModelConfig,
                     theta: float) -> Tuple[jax.Array, dict]:
    """MLA chunk verify over paged latent planes, all slots at once:
    scatter the chunks' latents, gather + expand each row's logical
    view, attend with the per-row causal bias — mla_prefill_paged's
    math, batched over slots.  -> (out (B, C, d), cache)."""
    B, C, _ = x.shape
    q, c_kv, k_r = _mla_qkv(params, x, a)
    p2 = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    q_c, q_r = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_r = apply_rope(q_r, p2, theta)
    k_r = apply_rope(k_r[..., None, :], p2, theta)[..., 0, :]
    upd = paged_write_batch(cache, "c_kv_pages", c_kv, table, pos, n_tok)
    upd.update(paged_write_batch(cache, "k_r_pages", k_r, table, pos,
                                 n_tok))
    c2 = dict(cache)
    c2.update(upd)
    lat = paged_gather(c2, "c_kv_pages", table, c_kv.dtype)  # (B, S, r)
    rop = paged_gather(c2, "k_r_pages", table, k_r.dtype)    # (B, S, rope)
    S = lat.shape[1]
    k_c, v = _mla_expand(params, lat, a)
    q_full = jnp.concatenate([q_c, q_r], -1)
    k_full = jnp.concatenate(
        [k_c, jnp.broadcast_to(rop[..., None, :],
                               k_c.shape[:-1] + (a.qk_rope_dim,))], -1)
    scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    o = _attend_dense(q_full, k_full, v, _verify_bias(pos, S, C, 0), scale)
    o = o.reshape(B, C, -1) @ params["w_o"]
    return o, upd


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg: ModelConfig, a: AttnConfig, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "w_cross_q": dense_init(ks[0], (d, a.n_heads * a.head_dim), dtype),
        "w_k": dense_init(ks[1], (d, a.n_kv_heads * a.head_dim), dtype),
        "w_v": dense_init(ks[2], (d, a.n_kv_heads * a.head_dim), dtype),
        "w_o": dense_init(ks[3], (a.n_heads * a.head_dim, d), dtype),
    }


def cross_attn_apply(params: dict, x: jax.Array, enc: jax.Array,
                     a: AttnConfig) -> jax.Array:
    B, T, _ = x.shape
    S = enc.shape[1]
    q = (x @ params["w_cross_q"]).reshape(B, T, a.n_heads, a.head_dim)
    k = (enc @ params["w_k"]).reshape(B, S, a.n_kv_heads, a.head_dim)
    v = (enc @ params["w_v"]).reshape(B, S, a.n_kv_heads, a.head_dim)
    scale = 1.0 / math.sqrt(a.head_dim)
    pos_q = jnp.arange(T)
    pos_k = jnp.arange(S)
    o = attend(q, k, v, pos_q, pos_k, window=0, causal=False, scale=scale)
    return o.reshape(B, T, -1) @ params["w_o"]
