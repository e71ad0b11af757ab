"""Attention / MLP / norm / RoPE primitives shared by all architectures.

Attention comes in three compute paths:

* ``attention_chunked``: online-softmax over (q-chunk, kv-chunk) tiles in pure
  jnp.  ``unrolled=True`` uses Python loops and *skips* fully-masked causal
  tiles — this path is used by the dry-run cost lowering so HLO FLOPs are
  exact; ``unrolled=False`` uses ``lax.scan`` (compact HLO for full-config
  compiles and real training).
* ``decode_attention``: single-token attention against a (possibly
  sequence-sharded) KV cache, read only: the token's own row is attended
  beside it and written afterwards (``write_kv_rows``).
* Pallas flash attention (``repro.kernels``) — TPU target path, selected via
  ``ModelConfig.use_pallas``.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"       # normal | zeros | ones | small
    dtype: Optional[str] = None  # None => model dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


# ---------------------------------------------------------------------------
# norms + activations
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def group_norm_heads(x: jax.Array, scale: jax.Array, bias: jax.Array,
                     eps: float) -> jax.Array:
    """Per-head LayerNorm used by RWKV wkv output. x: (..., H, dh)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}[name]


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, pos: jax.Array, theta: float,
               mrope_sections: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """x: (B, S, H, dh); pos: (B, S) or (B, S, 3) for M-RoPE."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)                      # (dh/2,)
    if mrope_sections is not None:
        # M-RoPE: frequency bands split into (t, h, w) sections; each section
        # rotates by its own position stream.  pos: (B, S, 3).
        assert pos.ndim == 3 and pos.shape[-1] == 3
        sec = jnp.cumsum(jnp.array((0,) + tuple(mrope_sections)))
        band = jnp.searchsorted(sec[1:], jnp.arange(d_head // 2), side="right")
        band = jnp.clip(band, 0, 2)                        # (dh/2,) in {0,1,2}
        p = jnp.take_along_axis(
            pos.astype(jnp.float32)[:, :, None, :],
            band[None, None, :, None].astype(jnp.int32), axis=-1)[..., 0]
        angles = p[..., None, :] * freqs[None, None, None, :]  # (B,S,1,dh/2)
        angles = angles[..., 0, :][:, :, None, :]
    else:
        angles = (pos.astype(jnp.float32)[..., None] * freqs)[:, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked online-softmax attention
# ---------------------------------------------------------------------------

NEG_INF = -2.0 ** 30


def _tile_mask(q0: int, k0: int, cq: int, ck: int, window: Optional[int],
               dtype) -> jax.Array:
    qi = q0 + jnp.arange(cq)[:, None]
    ki = k0 + jnp.arange(ck)[None, :]
    m = ki <= qi
    if window is not None:
        m &= ki > qi - window
    return jnp.where(m, 0.0, NEG_INF).astype(dtype)


def _attend_tile(q, k, v, bias, scale, cap):
    # q: (B,cq,H,dh) k/v: (B,ck,KV,dh) bias: (cq,ck) fp32
    B, cq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, cq, KV, G, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    s = s + bias[None, None, None]
    m = jnp.max(s, axis=-1)                               # (B,KV,G,cq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(B, cq, H, dh), m.transpose(0, 3, 1, 2).reshape(B, cq, H), \
        l.transpose(0, 3, 1, 2).reshape(B, cq, H)


def _combine(acc, o, m, l):
    o0, m0, l0 = acc
    m1 = jnp.maximum(m0, m)
    a0 = jnp.exp(m0 - m1)
    a1 = jnp.exp(m - m1)
    o1 = o0 * a0[..., None].astype(o0.dtype) + o * a1[..., None].astype(o.dtype)
    return o1, m1, l0 * a0 + l * a1


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      chunk_q: int = 1024, chunk_k: int = 1024,
                      scale: Optional[float] = None,
                      logit_cap: Optional[float] = None,
                      unrolled: bool = False) -> jax.Array:
    """q: (B,S,H,dh); k,v: (B,S,KV,dh) -> (B,S,H,dh). Causal GQA attention."""
    B, S, H, dh = q.shape
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    cq, ck = min(chunk_q, S), min(chunk_k, S)
    if S % cq or S % ck:
        cq = ck = S   # odd lengths (tests/short prompts): one full tile
    nq, nk = S // cq, S // ck

    # flash-attention memory discipline for the jnp path: remat each tile so
    # backward recomputes scores from the (already-saved) chunk inputs
    # instead of saving O(S^2) probabilities — same trade the Pallas kernel
    # makes on TPU.
    tile = jax.checkpoint(
        lambda qb, kb, vb, bias: _attend_tile(qb, kb, vb, bias, scale,
                                              logit_cap))

    if unrolled:
        outs = []
        for qi in range(nq):
            q0 = qi * cq
            acc = (jnp.zeros((B, cq, H, dh), q.dtype),
                   jnp.full((B, cq, H), NEG_INF, jnp.float32),
                   jnp.zeros((B, cq, H), jnp.float32))
            qb = jax.lax.dynamic_slice_in_dim(q, q0, cq, axis=1)
            for ki in range(nk):
                k0 = ki * ck
                if causal and k0 > q0 + cq - 1:
                    continue  # fully masked tile: skipped => exact FLOPs
                if window is not None and k0 + ck - 1 < q0 - window + 1:
                    continue
                kb = jax.lax.dynamic_slice_in_dim(k, k0, ck, axis=1)
                vb = jax.lax.dynamic_slice_in_dim(v, k0, ck, axis=1)
                bias = _tile_mask(q0, k0, cq, ck, window, jnp.float32) \
                    if causal else jnp.zeros((cq, ck), jnp.float32)
                o, m, l = tile(qb, kb, vb, bias)
                acc = _combine(acc, o, m, l)
            o, m, l = acc
            outs.append(o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype))
        return jnp.concatenate(outs, axis=1)

    def outer(qi):
        q0 = qi * cq
        qb = jax.lax.dynamic_slice_in_dim(q, q0, cq, axis=1)

        def inner(acc, ki):
            k0 = ki * ck
            kb = jax.lax.dynamic_slice_in_dim(k, k0, ck, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, k0, ck, axis=1)
            qi_idx = q0 + jnp.arange(cq)[:, None]
            ki_idx = k0 + jnp.arange(ck)[None, :]
            m = jnp.ones((cq, ck), jnp.bool_)
            if causal:
                m &= ki_idx <= qi_idx
            if window is not None:
                m &= ki_idx > qi_idx - window
            bias = jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)
            o, mm, l = tile(qb, kb, vb, bias)
            return _combine(acc, o, mm, l), None

        acc0 = (jnp.zeros((B, cq, H, dh), q.dtype),
                jnp.full((B, cq, H), NEG_INF, jnp.float32),
                jnp.zeros((B, cq, H), jnp.float32))
        (o, m, l), _ = jax.lax.scan(inner, acc0, jnp.arange(nk))
        return o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)

    out = jax.lax.map(outer, jnp.arange(nq))               # (nq, B, cq, H, dh)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, dh)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array, *, scale: Optional[float] = None,
                     logit_cap: Optional[float] = None,
                     new_kv: Optional[Tuple[jax.Array, jax.Array,
                                            jax.Array]] = None) -> jax.Array:
    """One-token attention. q: (B,1,H,dh); caches: (B,S,KV,dh).

    ``new_kv = (k, v, idx)`` ((B,KV,dh), (B,KV,dh), (B,)) attends the
    token's own key and value in place of cache row ``idx`` without
    writing it: the same keys and softmax as attending over the cache
    with the row written, while the cache stays a read-only input.

    The cache sequence dim may be sharded over the ``model`` mesh axis
    (flash-decode style); XLA inserts the partial-softmax reductions.
    """
    B, S, KV, dh = k_cache.shape
    H = q.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    qg = q.reshape(B, KV, G, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) * scale
    if new_kv is not None:
        k_new, v_new, idx = new_kv
        at = (jnp.arange(S)[None, :] == idx.reshape(-1, 1)
              )[:, None, None, :]                           # (B,1,1,S)
        s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new).astype(
            jnp.float32) * scale
        s = jnp.where(at, s_new[..., None], s)
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)
    mask = (jnp.arange(S)[None, :] < cur_len.reshape(-1, 1)
            )[:, None, None, :]                             # (B,1,1,S)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if new_kv is None:
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    else:
        p_new = jnp.sum(jnp.where(at, p, 0.0), axis=-1)     # (B,KV,G)
        p = jnp.where(at, 0.0, p).astype(v_cache.dtype)
        o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache,
                       preferred_element_type=jnp.float32)
        o = (o + p_new[..., None] * v_new.astype(jnp.float32)[:, :, None]
             ).astype(v_new.dtype)
    return o.reshape(B, 1, H, dh)


# ---------------------------------------------------------------------------
# attention block (projections + rope + attend)
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig) -> dict:
    a = cfg.attention
    D, H, KV, dh = cfg.d_model, a.n_heads, a.n_kv_heads, a.d_head
    defs = {
        "wq": ParamDef((D, H, dh), ("d_model", "heads", None),
                       init="normal_in"),
        "wk": ParamDef((D, KV, dh), ("d_model", "kv_heads", None),
                       init="normal_in"),
        "wv": ParamDef((D, KV, dh), ("d_model", "kv_heads", None),
                       init="normal_in"),
        "wo": ParamDef((H, dh, D), ("heads", None, "d_model")),
    }
    if a.qkv_bias:
        defs["bq"] = ParamDef((H, dh), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((KV, dh), ("kv_heads", None), init="zeros")
        defs["bv"] = ParamDef((KV, dh), ("kv_heads", None), init="zeros")
    return defs


def attention_apply(cfg: ModelConfig, p: dict, x: jax.Array, *,
                    mode: str, pos: jax.Array,
                    cache: Optional[dict] = None,
                    unrolled: bool = False):
    """Returns (out, new_cache). cache = {"k","v": (B,Smax,KV,dh), "len": (B,)}

    In decode, ``new_cache`` holds only the step's rows ("k","v": (B,KV,dh),
    int8 scales, and "len"), for ``write_kv_rows``; with ``unrolled`` it is
    the whole written cache."""
    a = cfg.attention
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if a.mrope_sections is not None and pos.ndim == 2:
        pos = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
    q = apply_rope(q, pos, a.rope_theta, a.mrope_sections)
    k = apply_rope(k, pos, a.rope_theta, a.mrope_sections)

    new_cache = None
    kv_int8 = cfg.kv_dtype == "int8"
    if mode in ("train", "prefill"):
        o = attention_chunked(
            q, k, v, causal=True, window=a.window,
            chunk_q=cfg.attn_chunk, chunk_k=cfg.attn_chunk,
            scale=a.softmax_scale, logit_cap=a.logit_cap, unrolled=unrolled)
        if mode == "prefill":
            S = k.shape[1]
            if cache is not None:
                # write into the pre-sized decode buffer (window caches keep
                # only the trailing window).  Ring slots are mod-aligned
                # (token t lives at slot t % Sbuf) so decode's next write
                # lands on the oldest token — hence the roll when S is not
                # a multiple of the window.
                kc, vc = cache["k"], cache["v"]
                Sbuf = kc.shape[1]
                ks, vs = k, v
                k_sc = v_sc = None
                if kv_int8:
                    ks, k_sc = _kv_quantize(k)
                    vs, v_sc = _kv_quantize(v)
                if Sbuf < S:
                    shift = (S - Sbuf) % Sbuf
                    roll2 = lambda t: jnp.roll(t[:, -Sbuf:], -shift, axis=1)
                    dus = lambda buf, t: jax.lax.dynamic_update_slice_in_dim(
                        buf, roll2(t), 0, axis=1)
                else:
                    dus = lambda buf, t: jax.lax.dynamic_update_slice_in_dim(
                        buf, t, 0, axis=1)
                kc = dus(kc, ks)
                vc = dus(vc, vs)
                new_cache = {"k": kc, "v": vc,
                             "len": jnp.full((k.shape[0],), S, jnp.int32)}
                if kv_int8:
                    new_cache["k_scale"] = dus(cache["k_scale"], k_sc)
                    new_cache["v_scale"] = dus(cache["v_scale"], v_sc)
            else:
                new_cache = {"k": k, "v": v,
                             "len": jnp.full((k.shape[0],), S, jnp.int32)}
                if kv_int8:
                    ks, k_sc = _kv_quantize(k)
                    vs, v_sc = _kv_quantize(v)
                    new_cache.update({"k": ks, "v": vs,
                                      "k_scale": k_sc, "v_scale": v_sc})
    else:  # decode: one token per batch row, at that row's slot
        assert cache is not None and q.shape[1] == 1
        cur = cache["len"]                                  # (B,)
        Sbuf = cache["k"].shape[1]
        idx = decode_slot(cfg, cur, Sbuf)
        eff = jnp.minimum(cur + 1, Sbuf)
        rows = {"k": k[:, 0], "v": v[:, 0]}
        if kv_int8:
            kq, k_sc = _kv_quantize(k)
            vq, v_sc = _kv_quantize(v)
            rows = {"k": kq[:, 0], "v": vq[:, 0],
                    "k_scale": k_sc[:, 0], "v_scale": v_sc[:, 0]}
        rows["len"] = cur + 1
        if unrolled:
            # dry-run cost path: a per-layer cache, written by one scatter
            # and attended over whole, as its cost was always counted
            b = jnp.arange(x.shape[0])
            new_cache = {key: t if key == "len" else
                         cache[key].at[b, idx].set(t)
                         for key, t in rows.items()}
            kd, vd = _kv_view(new_cache, x.dtype)
            o = decode_attention(q, kd, vd, eff, scale=a.softmax_scale,
                                 logit_cap=a.logit_cap)
        else:
            # the cache is read only; the caller writes the returned rows
            # (``write_kv_rows``), once for every stacked layer
            kd, vd = _kv_view(cache, x.dtype)
            kn, vn = _kv_view(rows, x.dtype)
            o = decode_attention(q, kd, vd, eff, scale=a.softmax_scale,
                                 logit_cap=a.logit_cap, new_kv=(kn, vn, idx))
            new_cache = rows
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, new_cache


def attention_cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    a = cfg.attention
    S = min(max_len, a.window) if a.window is not None else max_len
    store = cfg.kv_dtype or None
    kv = ParamDef((batch, S, a.n_kv_heads, a.d_head),
                  ("batch", "kv_seq", "kv_heads", None), dtype=store)
    defs = {"k": kv, "v": kv,
            "len": ParamDef((batch,), ("batch",), init="zeros",
                            dtype="int32")}
    if cfg.kv_dtype == "int8":
        sc = ParamDef((batch, S, a.n_kv_heads),
                      ("batch", "kv_seq", "kv_heads"), init="ones",
                      dtype="float32")
        defs["k_scale"] = sc
        defs["v_scale"] = sc
    return defs


KV_ROW_KEYS = ("k", "v", "k_scale", "v_scale")  # written one row per step


def decode_slot(cfg: ModelConfig, cur: jax.Array, Sbuf: int) -> jax.Array:
    """The cache row a decode step writes for lengths ``cur``: the ring
    slot of a window cache, else the next position (the last row once
    the buffer is full)."""
    a = cfg.attention
    if a.window is not None and Sbuf <= a.window:
        return jnp.mod(cur, Sbuf)
    return jnp.minimum(cur, Sbuf - 1)


def write_kv_rows(cfg: ModelConfig, cache: dict, rows: dict) -> dict:
    """``cache`` after one decode step: the KV rows of ``rows`` (the decode
    output of ``attention_apply``, with the leading layer axes of
    ``cache["len"]``) written at each batch row's slot.

    Every layer of a batch row holds the same length (prefill, install
    and decode write all layers together), so each batch row is one
    ``dynamic_update_slice`` across the layer axes.  On a donated cache
    that writes in place in whatever layout the leaf has; a scatter makes
    the TPU compiler copy the whole leaf to a row-major layout and back.
    """
    cur = cache["len"]
    lead = cur.ndim - 1
    idx = decode_slot(cfg, cur, cache["k"].shape[cur.ndim])[(0,) * lead]
    out = {"len": rows["len"]}
    for key in KV_ROW_KEYS:
        if key not in cache:
            continue
        leaf, new = cache[key], rows[key]
        for b in range(cur.shape[-1]):
            row = jnp.expand_dims(new[(slice(None),) * lead + (b,)],
                                  (lead, lead + 1))
            start = (0,) * lead + (b, idx[b]) + (0,) * (leaf.ndim - lead - 2)
            leaf = jax.lax.dynamic_update_slice(leaf, row.astype(leaf.dtype),
                                                start)
        out[key] = leaf
    return out


def _kv_view(c: dict, dtype):
    """The (k, v) attention reads from cache leaves ``c``."""
    if "k_scale" in c:
        return (_kv_dequantize(c["k"], c["k_scale"], dtype),
                _kv_dequantize(c["v"], c["v_scale"], dtype))
    return c["k"], c["v"]


def _kv_quantize(t: jax.Array):
    """Per-(token, head) symmetric int8. t: (B,S,KV,dh)."""
    scale = jnp.maximum(jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1),
                        1e-6) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequantize(q: jax.Array, scale: jax.Array, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w1": ParamDef((D, F), ("d_model", "d_ff")),
        "w3": ParamDef((D, F), ("d_model", "d_ff")),
        "w2": ParamDef((F, D), ("d_ff", "d_model")),
    }


def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["w1"])
    u = jnp.einsum("bsd,df->bsf", x, p["w3"])
    return jnp.einsum("bsf,fd->bsd", act_fn(cfg.act)(g) * u, p["w2"])
