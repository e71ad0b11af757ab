"""step_host_p50_ms (engine step): per ``serve.step`` span that starts in
the window, its duration less the time of the ``serve.decode.sync``
spans inside it (the host waiting on the decode's result): the step's
host time, whatever the host spent it on.  The median over those
steps; None for a program that has no such span."""
import bisect

from chipbench.readlib import obs_spans, percentile


def read(ctx):
    spans = obs_spans(ctx, {"serve.step", "serve.decode.sync"})
    syncs = [(s[1], s[2]) for s in spans if s[0] == "serve.decode.sync"]
    sync_starts = [s for s, _ in syncs]
    vals = []
    for name, start, end, _ in spans:
        if name != "serve.step" or not ctx.w0 <= start < ctx.w1:
            continue
        lo = bisect.bisect_left(sync_starts, start)
        hi = bisect.bisect_right(sync_starts, end)
        vals.append(end - start - sum(e - s for s, e in syncs[lo:hi]))
    p = percentile(vals, 50)
    return None if p is None else 1e3 * p
