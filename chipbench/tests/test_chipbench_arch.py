"""A configuration's family is found by the name its ``reference``
gives: the qwen2 module's weights, program configs and counts are held
at their values from before they moved to ``archs/qwen2.py``, and a
family that is new files only runs a whole cell (toy size, CPU)."""
import _paths  # noqa: F401
import hashlib
import os
import shutil

import jax
import numpy as np
import pytest

import tiny
from chipbench import driver, flops, run, spec, weights

CONFIGS = ["qwen2-0.5b.paged-leaf", "qwen2-0.5b.resident", "qwen2.5-14b-l8"]


def _load(name):
    return spec.load_json(spec.config_path(name))


def _digest(tree) -> str:
    """SHA-256 over every leaf in flatten order: path, dtype, shape,
    bytes."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# digests of weights.init before the tree moved to archs/qwen2.py
WEIGHTS = [
    (True, 7,
     "f6ba939a21d15001df771e2f3b94e4d569bc8dd9230f967e6d887e8780c6e9e0"),
    (False, 7,
     "91a7228cc02b5a21c804289f12aea3829a561ac82cc0a566ce64a5812ac71922"),
    (True, 2**33 + 2**31 + 12345,
     "8208e38ee382a78b34b49ec511e107b843e7af80a2848ab7a9f4dfb2f8383a0f"),
]


@pytest.mark.parametrize("tied,seed,want", WEIGHTS,
                         ids=["tied", "untied", "high_word"])
def test_weights_are_bit_identical(tied, seed, want):
    cfg = dict(tiny.CONFIG, tie_word_embeddings=tied)
    assert _digest(weights.init(cfg, seed)) == want


def _model(name, n, d, f, v, heads, kv, dh, tied, eps):
    from repro.configs.base import AttentionCfg, ModelConfig
    return ModelConfig(
        arch_id=name, family="dense", n_layers=n, d_model=d, d_ff=f,
        vocab=v,
        attention=AttentionCfg(n_heads=heads, n_kv_heads=kv, d_head=dh,
                               qkv_bias=True, rope_theta=1e6),
        tie_embeddings=tied, norm_eps=eps, act="silu", dtype="bfloat16")


PROGRAM = {
    "qwen2-0.5b.paged-leaf": (24, 896, 4864, 151936, 14, 2, 64, True, 1e-6),
    "qwen2-0.5b.resident": (24, 896, 4864, 151936, 14, 2, 64, True, 1e-6),
    "qwen2.5-14b-l8": (8, 5120, 13824, 152064, 40, 8, 128, False, 1e-5),
}


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_field_by_field(name):
    assert driver.program_config(name, _load(name)) == \
        _model(name, *PROGRAM[name])


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_tree_is_the_programs(name):
    cfg = _load(name)
    driver.check_param_layout(driver.program_config(name, cfg), cfg)


# every count before the counts moved to archs/qwen2.py
Q05 = {"params": 494032768, "layer_params": 14912384, "kv_row_bytes": 12288,
       "prefill": [988008448, 186309050368, 778242260992, 2604765184000],
       "decode_flops": 4338503680, "decode_bytes": 1043324672,
       "page_live": 12582912}
COUNTS = {
    "qwen2-0.5b.paged-leaf": Q05,
    "qwen2-0.5b.resident": Q05,
    "qwen2.5-14b-l8": {
        "params": 3759289344, "layer_params": 275268608,
        "kv_row_bytes": 32768,
        "prefill": [5961318400, 1134375731200, 4597256028160,
                    14304049889280],
        "decode_flops": 24581406720, "decode_bytes": 6108841984,
        "page_live": 33554432},
}


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_are_unchanged(name):
    cfg, want = _load(name), COUNTS[name]
    ctx = [1, 100, 300, 4096]
    got = {"params": flops.params(cfg),
           "layer_params": flops.layer_params(cfg),
           "kv_row_bytes": flops.kv_row_bytes(cfg),
           "prefill": [flops.prefill_flops(cfg, p)
                       for p in (1, 256, 1024, 3072)],
           "decode_flops": flops.decode_flops(cfg, ctx),
           "decode_bytes": flops.decode_bytes(cfg, ctx),
           "page_live": flops.page_live_bytes(cfg, 1024)}
    assert got == want
    assert all(type(v) is int for v in got.values() if not
               isinstance(v, list))


def _serve(reference: str):
    """A whole toy run whose configuration names ``reference``: the
    served tokens of the window's requests, and the numbers compared."""
    cfg = dict(tiny.CONFIG, reference=reference)
    reqs, numbers = {}, {}

    def keep_requests(eng):
        submit = eng.submit

        def rec(req):
            if req.rid >= 0:
                reqs[req.rid] = req
            submit(req)
        eng.submit = rec

    def keep_numbers(nums):
        numbers.update(nums)
        return nums

    args = run.parse(["--workload", "toy", "--seed", str(2**31 + 41),
                      "--seconds", "1.2", "--trace", "0"])
    result, lines = run.run(args, require_tpu=False,
                            resolved=tiny.resolved(False, config=cfg),
                            hooks={"engine": keep_requests,
                                   "numbers": keep_numbers})
    assert result["correct"], lines
    return {rid: list(r.out_tokens) for rid, r in reqs.items()}, \
        result["check"]["max_logit_gap"]["value"], numbers


def _family_dir(tmp_path, monkeypatch, name, kinds):
    """A benchmark directory holding ``qwen2``'s files of ``kinds``
    under the new family ``name``, and ``spec`` pointed at it."""
    for kind in kinds:
        os.makedirs(tmp_path / kind, exist_ok=True)
        shutil.copy(os.path.join(spec.BENCH_DIR, kind, "qwen2.py"),
                    tmp_path / kind / f"{name}.py")
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))


def test_a_family_is_new_files_only(tmp_path, monkeypatch):
    tokens, gap, numbers = _serve("qwen2")
    _family_dir(tmp_path, monkeypatch, "dense_copy",
                ("archs", "references"))
    tokens_copy, gap_copy, numbers_copy = _serve("dense_copy")
    assert tokens and sum(map(len, tokens.values())) > 20
    assert tokens_copy == tokens
    assert gap_copy == gap
    assert numbers_copy == numbers and numbers["replayed_requests"] > 0


@pytest.mark.parametrize("kinds", [(), ("references",)],
                         ids=["no_files", "reference_only"])
def test_unknown_family_exits_2(tmp_path, monkeypatch, capsys, kinds):
    _family_dir(tmp_path, monkeypatch, "no_such_family", kinds)
    cfg = dict(tiny.CONFIG, reference="no_such_family")
    rc = run.main(["--workload", "toy", "--seed", "1", "--seconds", "1",
                   "--trace", "0"], require_tpu=False,
                  resolved=tiny.resolved(False, config=cfg))
    assert rc == 2
    assert capsys.readouterr().out == ""
