"""Device time of the catch-up replay (``_catchup_impl``: the masked
per-row decode rounds, the v head and the fused combine) per step, in ms,
from the trace."""
import layers


def read(ctx):
    return layers.ms_per_step(ctx, layers.CATCHUP)
