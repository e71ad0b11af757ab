"""Plain float32 Qwen2 forward: the reference the served logits are
compared with.

Follows the published Qwen2 decoder (``Qwen2ForCausalLM`` in Hugging
Face transformers): RMSNorm before attention and MLP, GQA attention with
bias on Q/K/V and none on O, rotary embeddings on the two halves of
each head (``rotate_half``), causal softmax scaled by ``1/sqrt(head
dim)``, gated SiLU MLP, final RMSNorm and the output head (the
embedding when ``tie_word_embeddings``).  No cache, no batching, no
kernels: one sequence at a time, every matmul at
``Precision.HIGHEST`` so a TPU computes it in float32.

Weights come in the benchmark's tree (``chipbench/archs/qwen2.py``), which
stacks the layers on a leading axis and stores each RMSNorm gain as its
offset from 1, so the gain applied here is ``1 + w``.  The tree is made
by the benchmark from the seed; nothing of the program is imported.

To fit a 16 GB chip beside the bfloat16 weights, layers run one at a
time (``lax.scan`` over the stacked axis, each layer cast to float32
inside the step), attention runs in blocks of query rows, and the head
runs in blocks of the vocabulary for the requested positions only.

``quant="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with one scale per tensor (the nearest precision below the
configuration's bfloat16), accumulation stays float32.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q(x, quant: Optional[str]):
    """Matmul operand in the compute precision: float32, or float8
    e4m3 with a per-tensor scale for the control."""
    x = x.astype(jnp.float32)
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """x: (S, heads, dh); Hugging Face ``rotate_half`` convention."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]     # (S, dh/2)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, quant, x, p):
    S = x.shape[0]
    h_n = cfg["num_attention_heads"]
    kv_n = cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    pos = jnp.arange(S)
    f32 = lambda a: a.astype(jnp.float32)

    h = _rms(x, p["ln1"], eps)
    a = p["attn"]
    q = _mm("sd,dhk->shk", h, a["wq"], quant) + f32(a["bq"])
    k = _mm("sd,dhk->shk", h, a["wk"], quant) + f32(a["bk"])
    v = _mm("sd,dhk->shk", h, a["wv"], quant) + f32(a["bv"])
    q = _rope(q, pos, theta)
    k = _rope(k, pos, theta)
    dh = q.shape[-1]
    g = h_n // kv_n
    scale = 1.0 / np.sqrt(dh)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, 0)
        qb = qb.reshape(Q_BLOCK, kv_n, g, dh)
        s = _mm("qkgd,skd->kgqs", qb, k, quant) * scale
        mask = (jnp.arange(S)[None, :] <= (q0 + jnp.arange(Q_BLOCK))[:, None])
        s = jnp.where(mask[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = _mm("kgqs,skd->qkgd", w, v, quant)
        return o.reshape(Q_BLOCK, h_n, dh)

    o = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK))
    o = o.reshape(S, h_n, dh)
    x = x + _mm("shk,hkd->sd", o, a["wo"], quant)
    h = _rms(x, p["ln2"], eps)
    m = p["ffn"]
    up = jax.nn.silu(_mm("sd,df->sf", h, m["w1"], quant)) \
        * _mm("sd,df->sf", h, m["w3"], quant)
    return x + _mm("sf,fd->sd", up, m["w2"], quant)


def _vocab_blocks(v: int) -> int:
    return next(n for n in (8, 4, 2, 1) if v % n == 0)


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(weights, tokens, sel, cfg_items, quant):
    cfg = dict(cfg_items)
    x = jnp.take(weights["emb"], tokens, axis=0).astype(jnp.float32)

    def body(x, p):
        return _layer(cfg, quant, x, p), None

    x, _ = jax.lax.scan(body, x, weights["groups"]["b0"])
    x = _rms(jnp.take(x, sel, axis=0), weights["final_ln"],
             cfg["rms_norm_eps"])
    head = weights["emb"] if cfg["tie_word_embeddings"] \
        else weights["lm_head"]
    nb = _vocab_blocks(head.shape[0])
    hb = head.reshape(nb, head.shape[0] // nb, head.shape[1])
    out = jax.lax.map(lambda w: _mm("nd,vd->nv", x, w, quant), hb)
    return out.transpose(1, 0, 2).reshape(x.shape[0], head.shape[0])


def check_config(cfg: dict, seq_len: int) -> None:
    """Raise unless this reference implements ``cfg`` as published."""
    if cfg.get("model_type") != "qwen2":
        raise ValueError(f"qwen2 reference got {cfg.get('model_type')!r}")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("qwen2 reference implements silu only")
    if cfg.get("use_sliding_window", False):
        raise ValueError("qwen2 reference implements global attention only")
    if seq_len % Q_BLOCK:
        raise ValueError(f"sequence length {seq_len} is not a multiple "
                         f"of {Q_BLOCK}")


def logits(weights, cfg: dict, tokens: np.ndarray, sel: np.ndarray,
           quant: Optional[str] = None) -> jax.Array:
    """Float32 logits ``(len(sel), vocab)`` of the next token after each
    position in ``sel`` of the sequence ``tokens`` (padded at the end to
    a multiple of the query block; causal, so padding changes nothing
    before it)."""
    check_config(cfg, len(tokens))
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "tie_word_embeddings")
    items = tuple((k, cfg[k]) for k in keys)
    return _logits(weights, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(sel, jnp.int32), items, quant)
