"""How late the load generator issued ticks past their due time, 95th
percentile over the window's ticks, in ms (open loop only)."""


def read(ctx):
    if ctx["traffic"]["loop"] != "open":
        return None
    return ctx["lat"]["late_p95_ms"]
