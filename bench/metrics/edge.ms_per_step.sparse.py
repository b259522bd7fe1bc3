"""Device time of the edge step's programs (record, masked decode, u head)
per step, in ms, from the trace."""
import layers


def read(ctx):
    return layers.ms_per_step(ctx, layers.EDGE)
