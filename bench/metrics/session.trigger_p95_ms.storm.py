"""From a token's issue to its trigger decision on the host, 95th
percentile over all tokens of the traced run's window, in ms (host
clock)."""


def read(ctx):
    return ctx["lat"]["trigger_p95_ms"]
