"""Least time of the edge work (B tokens decoded, recorded and scored;
weights once a step) over the edge programs' device time, in %."""
import layers


def read(ctx):
    return layers.roofline(ctx, layers.EDGE, layers.edge_work(ctx))
