"""kv_bytes_per_req_mb (KV memory plane): bytes the tiered store spilled
to the cold tier plus bytes it staged back to the device while the
profiler was on, per request admitted in that time, in 1e6 bytes.  A
count: it repeats exactly for one page geometry."""


def read(ctx):
    if ctx.store0 is None:
        return None
    moved = sum(ctx.store1[k] - ctx.store0[k]
                for k in ("spill_bytes_physical", "h2c_bytes"))
    admitted = sum(1 for r in ctx.recs if r.req is not None
                   and ctx.t0 <= r.req.t_admit_pc < ctx.t1)
    if admitted == 0 or moved <= 0:
        return None
    return moved / admitted / 1e6
