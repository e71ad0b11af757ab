"""queue_wait_p50_ms (admission): median, over every request due in the
window that the engine admitted, of admission (the program's
``Request.t_admit_pc``) minus the due time."""
from chipbench.readlib import percentile, window_recs


def read(ctx):
    vals = [r.req.t_admit_pc - r.due_pc for r in window_recs(ctx)
            if r.req is not None and r.req.t_admit_pc > 0.0]
    p = percentile(vals, 50)
    return None if p is None else 1e3 * p
