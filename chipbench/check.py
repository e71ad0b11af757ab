"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the one with the most served tokens,
is replayed through the configuration's plain float32 reference: each
prompt followed by the tokens the program served, in one causal pass.
At every served position the reference gives its best logit and the
logit of the token the program served there.  The number compared is
the widest gap between the two (``max_logit_gap``): zero where the
program's greedy token is the reference's, and small where rounding
made it pick a near-tie.

The control (``control_gap``) reads the same number for the token that
the reference computed in float8 puts first at each position; the limit
lies between the program's readings and the control's (``PERF.md``).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

SAMPLE_TOKENS = 1024    # served tokens the replayed sample reaches ...
SAMPLE_REQUESTS = 8     # ... or this many requests, whichever comes first


def sample(recs, seed: int, target_tokens: int, max_requests: int) -> list:
    """The served requests to replay: the longest, then others in seeded
    order until ``target_tokens`` served tokens or ``max_requests``."""
    served = [r for r in recs if r.served]
    if not served:
        return []
    served.sort(key=lambda r: (-len(r.req.out_tokens), r.arr.rid))
    rest = served[1:]
    order = np.random.default_rng([int(seed), 5]).permutation(len(rest))
    out = [served[0]]
    total = len(served[0].req.out_tokens)
    for i in order:
        if total >= target_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        total += len(rest[i].req.out_tokens)
    return out


def _replay(prompt: np.ndarray, served: List[int], seq_len: int,
            n_sel: int):
    """Tokens of one teacher-forced pass and the positions whose
    next-token logits decide each served token."""
    toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    if len(toks) > seq_len or len(served) > n_sel:
        raise ValueError(f"request of {len(toks)} tokens / {len(served)} "
                         f"served exceeds the replay shape "
                         f"({seq_len}, {n_sel})")
    padded = np.zeros(seq_len, np.int32)
    padded[:len(toks)] = toks
    sel = np.zeros(n_sel, np.int32)
    sel[:len(served)] = len(prompt) - 1 + np.arange(len(served))
    tok = np.zeros(n_sel, np.int32)
    tok[:len(served)] = served
    return padded, sel, tok


def gaps(ref, weights, cfg: dict, prompt: np.ndarray, served: List[int],
         seq_len: int, n_sel: int, control: bool = False
         ) -> Dict[str, np.ndarray]:
    """Per served position: ``gap`` of the served token below the
    reference's best, and with ``control`` the ``control_gap`` of the
    float8 reference's first choice."""
    import jax.numpy as jnp
    padded, sel, tok = _replay(prompt, served, seq_len, n_sel)
    n = len(served)
    lg = ref.logits(weights, cfg, padded, sel)
    best = jnp.max(lg, axis=-1)
    at = jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], axis=-1)[:, 0]
    out = {"gap": np.asarray(best - at)[:n]}
    if control:
        lc = ref.logits(weights, cfg, padded, sel, quant="fp8")
        pick = jnp.argmax(lc, axis=-1)
        at_c = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        out["control_gap"] = np.asarray(best - at_c)[:n]
    return out


def replay_shape(cell: dict, mix: dict) -> tuple:
    """One replay shape for every request of the cell, so the reference
    compiles once: the cell's ``max_len`` rounded up to the reference's
    block, and room for the mix's longest output."""
    seq = -(-int(cell["max_len"]) // 512) * 512
    n_sel = -(-int(mix["output_len"]["max"]) // 128) * 128
    return seq, n_sel


def readings(ref, weights, cfg: dict, cell: dict, mix: dict, recs,
             seed: int, control: bool = False) -> Dict[str, float]:
    """The numbers compared, over the seeded sample of ``recs``."""
    picked = sample(recs, seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    seq, n_sel = replay_shape(cell, mix)
    worst = 0.0
    worst_c = 0.0
    tokens = 0
    for r in picked:
        g = gaps(ref, weights, cfg, r.arr.prompt, list(r.req.out_tokens),
                 seq, n_sel, control=control)
        worst = max(worst, float(g["gap"].max()))
        if control:
            worst_c = max(worst_c, float(g["control_gap"].max()))
        tokens += len(g["gap"])
    out = {"max_logit_gap": worst, "replayed_requests": len(picked),
           "replayed_tokens": tokens}
    if control:
        out["control_gap"] = worst_c
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Dict[str, dict]:
    """Each compared number beside its limit."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
