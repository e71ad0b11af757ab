"""The plain float32 reference agrees with what the serving engine
computes through its cache, paged and resident, at a toy size: the
next-token logits of every prefill and every decode step."""
import _paths  # noqa: F401
import numpy as np
import pytest

import tiny
from chipbench import driver, spec, traffic_gen, weights


def _serve_with_logits(paged: bool, seed: int = 7):
    r = tiny.resolved(paged)
    cfg, cell = r["config"], r["cell"]
    pcfg = driver.program_config("toy", cfg)
    driver.check_param_layout(pcfg, cfg)
    params = weights.init(cfg, seed)
    eng = driver.build_engine(pcfg, params, cfg, cell)
    seen = {}                     # (rid, token index) -> logits row
    prefill, decode = eng.prefill_1, eng.decode
    by_prompt = {}

    def rec_prefill(p, batch, caches):
        out = prefill(p, batch, caches)
        by_prompt[tuple(np.asarray(batch["tokens"][0]))] = \
            np.asarray(out[1][0], np.float32)
        return out

    def rec_decode(p, batch, caches):
        active = [(s, q.rid, len(q.out_tokens))
                  for s, q in enumerate(eng.slot_req) if q is not None]
        out = decode(p, batch, caches)
        lg = np.asarray(out[1], np.float32)
        for s, rid, n in active:
            seen[(rid, n)] = lg[s]
        return out

    eng.prefill_1, eng.decode = rec_prefill, rec_decode
    arrivals = traffic_gen.schedule(r["traffic"], 20.0, 0.3,
                                    cfg["vocab_size"], seed)
    import time
    t = time.perf_counter()
    recs = driver.Loop(eng).run(arrivals, t, t + 10.0, t + 120.0)
    driver.close_engine(eng)
    return r, params, recs, seen, by_prompt


@pytest.mark.parametrize("paged", [False, True], ids=["resident", "paged"])
def test_reference_matches_engine_logits(paged):
    r, params, recs, seen, by_prompt = _serve_with_logits(paged)
    cfg = r["config"]
    ref = spec.reference(cfg["reference"])
    assert recs and all(x.served for x in recs)
    checked = 0
    for x in recs:
        prompt, out = x.arr.prompt, list(x.req.out_tokens)
        rows = [by_prompt[tuple(prompt)]] + [seen[(x.arr.rid, j)]
                                             for j in range(1, len(out))]
        toks = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        padded = np.zeros(512, np.int32)
        padded[:len(toks)] = toks
        sel = len(prompt) - 1 + np.arange(len(out))
        want = np.asarray(ref.logits(params, cfg, padded, sel))
        got = np.stack(rows)
        # bfloat16 activations and caches against float32: a logit of
        # scale ~1 keeps about 3 significant digits through two layers
        np.testing.assert_allclose(got, want, atol=0.03, rtol=0)
        # and the engine's greedy token is the reference's best, or a
        # near-tie within that rounding
        best = want.max(-1)
        at = want[np.arange(len(out)), out]
        assert (best - at).max() <= 0.03
        checked += len(out)
    assert checked > 20


def test_reference_fp8_control_is_coarser():
    """The control (float8 operands) departs from float32 by far more
    than the engine's bfloat16 does."""
    r, params, recs, seen, by_prompt = _serve_with_logits(False, seed=8)
    cfg = r["config"]
    ref = spec.reference(cfg["reference"])
    x = max(recs, key=lambda q: len(q.req.out_tokens))
    out = list(x.req.out_tokens)
    toks = np.concatenate([x.arr.prompt, np.asarray(out[:-1], np.int32)])
    padded = np.zeros(512, np.int32)
    padded[:len(toks)] = toks
    sel = len(x.arr.prompt) - 1 + np.arange(len(out))
    f32 = np.asarray(ref.logits(params, cfg, padded, sel))
    f8 = np.asarray(ref.logits(params, cfg, padded, sel, quant="fp8"))
    eng = np.stack([by_prompt[tuple(x.arr.prompt)]]
                   + [seen[(x.arr.rid, j)] for j in range(1, len(out))])
    assert np.abs(f8 - f32).max() > 3 * np.abs(eng - f32).max()
