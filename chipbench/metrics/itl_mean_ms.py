"""itl_mean_ms: mean of every gap between consecutive output tokens of
every request, for tokens stamped inside the window: the time a request
waits per token, admission stalls of the whole batch included."""
from chipbench.readlib import token_gaps


def read(ctx):
    gaps = token_gaps(ctx)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
