"""From a triggered token's issue to its corrected fhat on the host, 95th
percentile over the traced run's triggered tokens, in ms (host clock)."""


def read(ctx):
    return ctx["lat"]["fhat_p95_ms"]
