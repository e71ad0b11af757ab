"""kv_page_wait_p50_ms (KV memory plane): per request due in the window,
from the end of its ``serve.prefill`` span (prefill, pack and spill
done) to its ``serve.first_token`` instant (fetch and install done);
the median over those requests.  The obs tracer is on for the whole
traced run, so every request of the window counts."""
from chipbench.readlib import obs_instants, obs_spans, percentile, window_recs


def read(ctx):
    if ctx.store0 is None:
        return None
    rids = {r.arr.rid for r in window_recs(ctx)}
    prefill_end = {s[3].get("rid"): s[2]
                   for s in obs_spans(ctx, {"serve.prefill"})}
    vals = [t - prefill_end[a["rid"]]
            for t, a in obs_instants(ctx, "serve.first_token")
            if a.get("rid") in rids and a["rid"] in prefill_end]
    p = percentile(vals, 50)
    return None if p is None else 1e3 * p
