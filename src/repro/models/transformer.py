"""Block composition for all assigned architectures.

Layers are grouped by ``cfg.block_pattern`` (e.g. ``("rec","rec","attn")``);
``n_layers // len(pattern)`` pattern groups are *stacked* (leading axis) and
applied with ``lax.scan`` (+ optional per-group remat); remainder layers are
applied unrolled as the "tail".  The ``unrolled=True`` path (dry-run cost
lowering) applies every group in a Python loop so ``cost_analysis`` sees each
layer's FLOPs (XLA counts a while-loop body once — see DESIGN.md §6).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv as rwkv_mod
from repro.models.layers import (ParamDef, attention_apply,
                                 attention_cache_defs, attention_defs,
                                 mlp_apply, mlp_defs, rms_norm,
                                 write_kv_rows)
from repro.sharding import constrain

# ---------------------------------------------------------------------------
# per-block param/cache definitions
# ---------------------------------------------------------------------------

def block_defs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    ln = ParamDef((cfg.d_model,), (None,), init="zeros")
    if kind == "attn":
        ffn = moe_mod.moe_defs(cfg) if cfg.moe is not None else mlp_defs(cfg)
        return {"ln1": ln, "attn": attention_defs(cfg), "ln2": ln, "ffn": ffn}
    if kind == "rwkv":
        return {"ln1": ln, "ln2": ln, "mix": rwkv_mod.rwkv_defs(cfg)}
    if kind == "rec":
        return {"ln1": ln, "rec": rglru_mod.rglru_defs(cfg),
                "ln2": ln, "ffn": mlp_defs(cfg)}
    raise ValueError(kind)


def block_cache_defs(cfg: ModelConfig, kind: str, batch: int,
                     max_len: int) -> Dict[str, Any]:
    if kind == "attn":
        return {"attn": attention_cache_defs(cfg, batch, max_len)}
    if kind == "rwkv":
        return {"mix": rwkv_mod.rwkv_state_defs(cfg, batch),
                "cm_x": ParamDef((batch, cfg.d_model), ("batch", None))}
    if kind == "rec":
        return {"rec": rglru_mod.rglru_state_defs(cfg, batch)}
    raise ValueError(kind)


def block_apply(cfg: ModelConfig, kind: str, p, x, *, mode: str,
                pos, cache=None, unrolled: bool = False, ctx=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        o, new_attn = attention_apply(
            cfg, p["attn"], h, mode=mode, pos=pos,
            cache=None if cache is None else cache["attn"], unrolled=unrolled)
        x = x + o
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            o, aux = moe_mod.moe_apply(cfg, p["ffn"], h, ctx=ctx)
        else:
            o = mlp_apply(cfg, p["ffn"], h)
        x = x + o
        new_cache = None if new_attn is None else {"attn": new_attn}
        return x, new_cache, aux
    if kind == "rwkv":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        o, tm_state = rwkv_mod.rwkv_time_mix(
            cfg, p["mix"], h, mode=mode,
            state=None if cache is None else cache["mix"], unrolled=unrolled)
        x = x + o
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        o, cm_x = rwkv_mod.rwkv_channel_mix(
            cfg, p["mix"], h, mode=mode,
            state=None if cache is None else {"cm_x": cache["cm_x"]})
        x = x + o
        new_cache = None
        if tm_state is not None:
            new_cache = {"mix": tm_state, "cm_x": cm_x}
        return x, new_cache, aux
    if kind == "rec":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        o, rec_state = rglru_mod.rglru_apply(
            cfg, p["rec"], h, mode=mode,
            state=None if cache is None else cache["rec"])
        x = x + o
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_apply(cfg, p["ffn"], h)
        new_cache = None if rec_state is None else {"rec": rec_state}
        return x, new_cache, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# whole-model parameter / cache trees
# ---------------------------------------------------------------------------

def _group_layout(cfg: ModelConfig) -> Tuple[int, int]:
    p = len(cfg.block_pattern)
    return cfg.n_layers // p, cfg.n_layers % p


def param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    n_groups, n_tail = _group_layout(cfg)
    group = {f"b{i}": block_defs(cfg, k)
             for i, k in enumerate(cfg.block_pattern)}

    def stack(d: ParamDef) -> ParamDef:
        return ParamDef((n_groups,) + d.shape, ("layers",) + d.logical,
                        init=d.init, dtype=d.dtype)

    defs: Dict[str, Any] = {
        "emb": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "d_model"),
                        init="embed"),
        "final_ln": ParamDef((cfg.d_model,), (None,), init="zeros"),
    }
    if n_groups:
        defs["groups"] = jax.tree.map(
            stack, group, is_leaf=lambda x: isinstance(x, ParamDef))
    if n_tail:
        defs["tail"] = {f"t{i}": block_defs(cfg, cfg.block_pattern[i])
                        for i in range(n_tail)}
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.vocab, cfg.d_model),
                                   ("vocab", "d_model"))
    return defs


def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    n_groups, n_tail = _group_layout(cfg)
    group = {f"b{i}": block_cache_defs(cfg, k, batch, max_len)
             for i, k in enumerate(cfg.block_pattern)}

    def stack(d: ParamDef) -> ParamDef:
        return ParamDef((n_groups,) + d.shape, ("layers",) + d.logical,
                        init=d.init, dtype=d.dtype)

    defs: Dict[str, Any] = {}
    if n_groups:
        defs["groups"] = jax.tree.map(
            stack, group, is_leaf=lambda x: isinstance(x, ParamDef))
    if n_tail:
        defs["tail"] = {f"t{i}": block_cache_defs(
            cfg, cfg.block_pattern[i], batch, max_len) for i in range(n_tail)}
    return defs


# ---------------------------------------------------------------------------
# materialisation helpers (abstract / logical / init)
# ---------------------------------------------------------------------------

def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_abstract(defs, cfg: ModelConfig):
    def one(d: ParamDef):
        dt = jnp.dtype(d.dtype or cfg.dtype)
        return jax.ShapeDtypeStruct(d.shape, dt)
    return jax.tree.map(one, defs, is_leaf=_is_def)


def tree_logical(defs):
    return jax.tree.map(lambda d: d.logical, defs, is_leaf=_is_def)


def _init_leaf(d: ParamDef, cfg: ModelConfig, key) -> jax.Array:
    dt = jnp.dtype(d.dtype or cfg.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    if d.init == "small":
        return (0.01 * jax.random.normal(key, d.shape)).astype(dt)
    if d.init == "decay":  # rwkv w0: spread of slow-to-fast decays
        n = int(np.prod(d.shape))
        v = jnp.linspace(-6.0, -3.0, n).reshape(d.shape)
        return v.astype(dt)
    if d.init == "lru_lambda":  # a in ~[0.9, 0.999]
        return jax.random.uniform(key, d.shape, jnp.float32,
                                  -9.0, -4.3).astype(dt)
    if d.init == "embed":
        return (0.02 * jax.random.normal(key, d.shape)).astype(dt)
    if d.init == "normal_in":
        fan = d.shape[0]
    elif d.init == "normal1":
        fan = d.shape[1]
    else:  # "normal": all-but-last is fan-in
        fan = max(1, int(np.prod(d.shape[:-1])))
    std = fan ** -0.5
    return (std * jax.random.normal(key, d.shape)).astype(dt)


def tree_init(defs, cfg: ModelConfig, key) -> Any:
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(d, cfg, k) for d, k in zip(leaves, keys)])


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    defs = cache_defs(cfg, batch, max_len)
    def one(d: ParamDef):
        dt = jnp.dtype(d.dtype or cfg.dtype)
        return jnp.zeros(d.shape, dt)
    return jax.tree.map(one, defs, is_leaf=_is_def)


# ---------------------------------------------------------------------------
# forward over the whole stack
# ---------------------------------------------------------------------------

def _write_decode_rows(cfg: ModelConfig, cache, rows):
    """A block's cache after a decode step, from ``block_apply``'s new
    cache: attention KV gets the step's row written in place; recurrent
    state, rewritten whole every step, is the new cache already."""
    if "attn" not in rows:
        return rows
    return {"attn": write_kv_rows(cfg, cache["attn"], rows["attn"])}


def apply_blocks(cfg: ModelConfig, params, x, *, mode: str, pos,
                 caches=None, unrolled: bool = False, ctx=None):
    """Run every block. Returns (x, new_caches, aux_total).

    Decode keeps the stacked attention KV out of the scan's outputs: each
    layer reads its K and V as scan inputs, the scan emits only the new
    rows, and ``write_kv_rows`` writes them in place after it (DESIGN.md
    §6.5).
    """
    n_groups, n_tail = _group_layout(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}

    def group_body(x, gp, gc):
        aux_g = jnp.zeros((), jnp.float32)
        new_gc = {}
        for i, kind in enumerate(cfg.block_pattern):
            c = None if gc is None else gc[f"b{i}"]
            x, nc, aux = block_apply(cfg, kind, gp[f"b{i}"], x, mode=mode,
                                     pos=pos, cache=c, unrolled=unrolled,
                                     ctx=ctx)
            # pin activations to (batch-sharded, replicated, replicated) so
            # SPMD propagation never falls back to replicated compute
            x = constrain(x, ("batch", None, None), ctx)
            if nc is not None:
                new_gc[f"b{i}"] = nc
            aux_g = aux_g + aux
        return x, (new_gc if new_gc else None), aux_g

    if n_groups:
        gparams = params["groups"]
        gcaches = caches.get("groups") if caches else None
        if unrolled:
            # dry-run cost path: Python loop so cost_analysis sees every
            # layer; keep the remat policy so FLOPs match the scanned path
            fn = jax.checkpoint(group_body) if cfg.remat == "block" \
                else group_body
            ncs = []
            for g in range(n_groups):
                gp = jax.tree.map(lambda t: t[g], gparams)
                gc = None if gcaches is None else jax.tree.map(
                    lambda t: t[g], gcaches)
                x, nc, aux = fn(x, gp, gc)
                aux_total = aux_total + aux
                ncs.append(nc)
            if ncs and ncs[0] is not None:
                new_caches["groups"] = jax.tree.map(
                    lambda *ts: jnp.stack(ts), *ncs)
        else:
            span = max(1, cfg.remat_span)
            if n_groups % span:
                span = 1

            def span_body(x, gp, gc):
                aux_sp = jnp.zeros((), jnp.float32)
                ncs_sp = []
                for j in range(span):
                    gpj = jax.tree.map(lambda t: t[j], gp)
                    gcj = None if gc is None else jax.tree.map(
                        lambda t: t[j], gc)
                    x, nc, aux = group_body(x, gpj, gcj)
                    ncs_sp.append(nc)
                    aux_sp = aux_sp + aux
                if ncs_sp and ncs_sp[0] is not None:
                    ncs_sp = jax.tree.map(lambda *ts: jnp.stack(ts), *ncs_sp)
                else:
                    ncs_sp = None
                return x, ncs_sp, aux_sp

            def scan_body(carry, xs):
                x, aux_acc = carry
                gp, gc = xs
                if span == 1:
                    fn = group_body
                    gp = jax.tree.map(lambda t: t[0], gp)
                    gc = None if gc is None else jax.tree.map(
                        lambda t: t[0], gc)
                    if cfg.remat == "block":
                        fn = jax.checkpoint(fn)
                    x, nc, aux = fn(x, gp, gc)
                else:
                    fn = span_body
                    if cfg.remat == "block":
                        fn = jax.checkpoint(fn)
                    x, nc, aux = fn(x, gp, gc)
                return (x, aux_acc + aux), nc

            resh = lambda t: t.reshape((n_groups // span, span)
                                       + t.shape[1:])
            xs = (jax.tree.map(resh, gparams),
                  None if gcaches is None else jax.tree.map(resh, gcaches))
            (x, aux_total), ncs = jax.lax.scan(scan_body, (x, aux_total), xs)
            if ncs is not None and jax.tree.leaves(ncs):
                if span > 1:
                    # un-chunk the (n_super, span, ...) cache stacking
                    unresh = lambda t: t.reshape((n_groups,) + t.shape[2:])
                    ncs = jax.tree.map(unresh, ncs)
                if mode == "decode":
                    ncs = {b: _write_decode_rows(cfg, gcaches[b], nc)
                           for b, nc in ncs.items()}
                new_caches["groups"] = ncs

    if n_tail:
        tcaches = caches.get("tail") if caches else None
        new_tail = {}
        for i in range(n_tail):
            kind = cfg.block_pattern[i]
            c = None if tcaches is None else tcaches[f"t{i}"]
            x, nc, aux = block_apply(cfg, kind, params["tail"][f"t{i}"], x,
                                     mode=mode, pos=pos, cache=c,
                                     unrolled=unrolled, ctx=ctx)
            aux_total = aux_total + aux
            if mode == "decode" and not unrolled:
                nc = _write_decode_rows(cfg, c, nc)
            if nc is not None:
                new_tail[f"t{i}"] = nc
        if new_tail:
            new_caches["tail"] = new_tail

    return x, (new_caches if new_caches else None), aux_total
