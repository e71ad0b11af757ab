"""kv_spill_p50_ms (KV memory plane): per request due in the window, the
duration of its ``kv.spill`` span (the packed page written to the cold
tier through the access path); the median over those requests.  None
for a cell without a pager, and for a program that has no such span."""
from chipbench.readlib import obs_spans, percentile, window_recs


def read(ctx):
    if ctx.store0 is None:
        return None
    rids = {r.arr.rid for r in window_recs(ctx)}
    vals = [end - start for _, start, end, args in obs_spans(ctx, {"kv.spill"})
            if args.get("rid") in rids]
    p = percentile(vals, 50)
    return None if p is None else 1e3 * p
