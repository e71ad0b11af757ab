"""ttft_p50_ms: median, over every request due in the window, of its
first token's stamp minus its due time.  A request that never got its
first token counts as waiting until the drain's grace ended."""
from chipbench.readlib import percentile, ttft_values


def read(ctx):
    p = percentile(ttft_values(ctx), 50)
    return None if p is None else 1e3 * p
