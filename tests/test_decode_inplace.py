"""The decode step updates the KV cache in place (DESIGN.md §6.5).

The stacked attention K and V stay out of the layer scan's outputs: the
scan emits each layer's new row, one scatter writes the rows into the
donated cache, and attention reads the old rows plus the token's own.
The oracle below is the step as it was before: each layer's cache slice
rides the scan as input and output, the row is written into it, and
attention reads the written slice.  Both must give the same tokens,
logits and cache tree, over caches whose batch rows hold different
lengths, past a window ring's wrap, with int8 KV, M-RoPE and recurrent
state.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduce_for_smoke
from repro.models import layers as L
from repro.models import lm
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.serving import Request, ServeEngine

KEY = jax.random.PRNGKey(5)
STEPS = 5

# ---------------------------------------------------------------------------
# oracle: the decode as a whole-cache scan input and output
# ---------------------------------------------------------------------------


def _oracle_attention(cfg, p, x, pos, cache):
    a = cfg.attention
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if a.mrope_sections is not None and pos.ndim == 2:
        pos = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
    q = L.apply_rope(q, pos, a.rope_theta, a.mrope_sections)
    k = L.apply_rope(k, pos, a.rope_theta, a.mrope_sections)
    cur = cache["len"]
    Sbuf = cache["k"].shape[1]
    idx = jnp.mod(cur, Sbuf) if a.window is not None and Sbuf <= \
        a.window else jnp.minimum(cur, Sbuf - 1)
    rows = jnp.arange(k.shape[0])
    new = {"len": cur + 1}
    if cfg.kv_dtype == "int8":
        kq, k_sc = L._kv_quantize(k)
        vq, v_sc = L._kv_quantize(v)
        new["k"] = cache["k"].at[rows, idx].set(kq[:, 0])
        new["v"] = cache["v"].at[rows, idx].set(vq[:, 0])
        new["k_scale"] = cache["k_scale"].at[rows, idx].set(k_sc[:, 0])
        new["v_scale"] = cache["v_scale"].at[rows, idx].set(v_sc[:, 0])
        kd = L._kv_dequantize(new["k"], new["k_scale"], x.dtype)
        vd = L._kv_dequantize(new["v"], new["v_scale"], x.dtype)
    else:
        new["k"] = kd = cache["k"].at[rows, idx].set(k[:, 0])
        new["v"] = vd = cache["v"].at[rows, idx].set(v[:, 0])
    o = L.decode_attention(q, kd, vd, jnp.minimum(cur + 1, Sbuf),
                           scale=a.softmax_scale, logit_cap=a.logit_cap)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), new


def _oracle_block(cfg, kind, p, x, pos, cache):
    if kind != "attn":
        x, nc, _ = T.block_apply(cfg, kind, p, x, mode="decode", pos=pos,
                                 cache=cache)
        return x, nc
    o, new = _oracle_attention(cfg, p["attn"],
                               L.rms_norm(x, p["ln1"], cfg.norm_eps), pos,
                               cache["attn"])
    x = x + o
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        o, _ = moe_mod.moe_apply(cfg, p["ffn"], h)
    else:
        o = L.mlp_apply(cfg, p["ffn"], h)
    return x + o, {"attn": new}


def _oracle_decode(cfg, params, batch, caches):
    x = lm.embed(cfg, params, batch)
    pos = lm.positions(cfg, batch)
    new = {}
    if "groups" in params:
        def body(x, xs):
            gp, gc = xs
            out = {}
            for i, kind in enumerate(cfg.block_pattern):
                b = f"b{i}"
                x, out[b] = _oracle_block(cfg, kind, gp[b], x, pos, gc[b])
            return x, out
        x, new["groups"] = jax.lax.scan(
            body, x, (params["groups"], caches["groups"]))
    if "tail" in params:
        new["tail"] = {}
        for t, p in params["tail"].items():
            kind = cfg.block_pattern[int(t[1:])]
            x, new["tail"][t] = _oracle_block(cfg, kind, p, x, pos,
                                              caches["tail"][t])
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return new, lm.unembed(cfg, params, x)[:, -1]


# ---------------------------------------------------------------------------
# cases
# ---------------------------------------------------------------------------


def _cfg(arch, **kw):
    return dataclasses.replace(reduce_for_smoke(get_config(arch)),
                               dtype="float32", **kw)


def _rg_attn_in_tail():
    cfg = _cfg("recurrentgemma-2b")
    return dataclasses.replace(cfg, block_pattern=("attn", "rec", "rec"),
                               n_layers=7)


# (config, max_len, prompt length of each batch row)
CASES = {
    "qwen2-0.5b": (lambda: _cfg("qwen2-0.5b"), 40, (5, 12, 21)),
    # window 32: rows wrap the ring before, during and after the steps
    "recurrentgemma-2b-ring": (lambda: _cfg("recurrentgemma-2b"), 48,
                               (29, 31, 40)),
    "qwen2-0.5b-int8": (lambda: _cfg("qwen2-0.5b", kv_dtype="int8"), 40,
                        (5, 12, 21)),
    "qwen2-vl-7b-mrope": (lambda: _cfg("qwen2-vl-7b"), 40, (7, 3, 16)),
    "rwkv6-1.6b": (lambda: _cfg("rwkv6-1.6b"), 40, (5, 12, 21)),
    # an attention block outside the scan, in the tail
    "recurrentgemma-2b-attn-tail": (_rg_attn_in_tail, 48, (9, 30, 37)),
    # two layer groups per scan step
    "qwen2-0.5b-span2": (lambda: _cfg("qwen2-0.5b", remat_span=2,
                                      remat="block"), 40, (5, 12, 21)),
}


def _batch_caches(cfg, params, max_len, lens):
    """Prefill each row alone and stack the rows' caches on the batch
    axis (axis 1 of stacked group leaves, 0 of tail leaves)."""
    prefill = jax.jit(lm.make_prefill_step(cfg))
    rows, first = [], []
    for r, n in enumerate(lens):
        toks = jax.random.randint(jax.random.fold_in(KEY, r), (1, n), 0,
                                  cfg.vocab)
        c, logits = prefill(params, {"tokens": toks},
                            T.init_cache(cfg, 1, max_len))
        rows.append(c)
        first.append(int(jnp.argmax(logits[0])))
    caches = {part: jax.tree.map(
        lambda *ts: jnp.concatenate(ts, axis=1 if part == "groups" else 0),
        *[c[part] for c in rows]) for part in rows[0]}
    return caches, jnp.asarray(first, jnp.int32)[:, None]


def _decode_batch(cfg, toks, pos):
    pos = pos[:, None]
    if cfg.attention is not None and cfg.attention.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
    return {"tokens": toks, "pos": pos}


@pytest.mark.parametrize("case", list(CASES))
def test_inplace_decode_matches_whole_cache_oracle(case):
    make, max_len, lens = CASES[case]
    cfg = make()
    params = T.tree_init(T.param_defs(cfg), cfg, KEY)
    caches, toks = _batch_caches(cfg, params, max_len, lens)
    ref = jax.tree.map(jnp.copy, caches)
    oracle = jax.jit(lambda p, b, c: _oracle_decode(cfg, p, b, c))
    decode = jax.jit(lm.make_decode_step(cfg), donate_argnums=(2,))
    pos = jnp.asarray(lens, jnp.int32)
    for _ in range(STEPS):
        batch = _decode_batch(cfg, toks, pos)
        ref, want = oracle(params, batch, ref)
        caches, got = decode(params, batch, caches)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        toks = jnp.argmax(want, axis=-1).astype(jnp.int32)[:, None]
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(got, axis=-1))[:, None], np.asarray(toks))
        pos = pos + 1
    assert jax.tree.structure(caches) == jax.tree.structure(ref)
    for path, got, want in zip(
            jax.tree_util.tree_flatten_with_path(caches)[0],
            jax.tree.leaves(caches), jax.tree.leaves(ref)):
        assert got.shape == want.shape and got.dtype == want.dtype, path
        if jnp.issubdtype(got.dtype, jnp.integer):   # lengths, int8 KV
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=str(path))
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=5e-5,
                                       err_msg=str(path))


# ---------------------------------------------------------------------------
# structure: no KV cache rides the scan; the engine donates it
# ---------------------------------------------------------------------------


def _scan_outputs(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield from (v.aval.shape for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scan_outputs(sub)


@pytest.mark.parametrize("case", ["qwen2-0.5b", "qwen2-0.5b-span2",
                                  "recurrentgemma-2b-ring"])
def test_decode_scan_carries_rows_not_the_cache(case):
    make, max_len, lens = CASES[case]
    cfg = make()
    B, a = len(lens), cfg.attention
    params = jax.eval_shape(lambda: T.tree_init(T.param_defs(cfg), cfg, KEY))
    caches = jax.eval_shape(lambda: T.init_cache(cfg, B, max_len))
    S = caches["groups"][f"b{cfg.block_pattern.index('attn')}"]["attn"][
        "k"].shape[2]
    batch = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    jaxpr = jax.make_jaxpr(lm.make_decode_step(cfg))(params, batch, caches)
    shapes = list(_scan_outputs(jaxpr.jaxpr))
    kv = (S, a.n_kv_heads, a.d_head)
    assert not [s for s in shapes if s[-3:] == kv], shapes
    assert (B, a.n_kv_heads, a.d_head) in [s[-3:] for s in shapes], shapes


@pytest.fixture
def _metrics_live():
    obs.default_registry().clear()
    obs.metrics.enable_live()
    yield obs.default_registry()
    obs.metrics.disable_live()
    obs.default_registry().clear()


def test_engine_step_donates_the_cache(_metrics_live):
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    params = T.tree_init(T.param_defs(cfg), cfg, KEY)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    rng = np.random.default_rng(0)
    for rid in range(2):
        eng.submit(Request(rid=rid, max_new=6,
                           prompt=rng.integers(0, 256, 5 + rid,
                                               dtype=np.int32)))
    assert eng.step() == 2                  # admits both, then decodes
    nbytes = sum(l.nbytes for l in jax.tree.leaves(eng.caches))
    counter = _metrics_live.counter("serve.decode.kv_donated_bytes")
    assert counter.value == nbytes
    for n in range(2, 5):
        before = jax.tree.leaves(eng.caches)
        assert eng.step() == 2
        assert all(l.is_deleted() for l in before)
        assert not any(l.is_deleted() for l in jax.tree.leaves(eng.caches))
        assert counter.value == n * nbytes
    assert eng.run_until_drained(max_steps=20) == 0
    assert all(len(r.out_tokens) == 6 for r in eng.done)


def test_serve_cli_metrics_carry_the_donated_bytes():
    from repro.launch.serve import main
    res = main(["--smoke", "--requests", "2", "--max-new", "3",
                "--slots", "2", "--prompt-len", "6", "--metrics"])
    assert res["metrics"]["serve.decode.kv_donated_bytes"] > 0
