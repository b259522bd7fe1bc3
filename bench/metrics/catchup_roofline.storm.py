"""Least time of the useful replay (triggered rows' backlogs, weights
once a call) over the catch-up's device time, in %."""
import layers


def read(ctx):
    return layers.roofline(ctx, layers.CATCHUP, layers.catchup_work(ctx))
