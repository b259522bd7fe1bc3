"""Useful model FLOPs of the traced window over window x the chip's
bf16 peak, in %."""
import layers


def read(ctx):
    return layers.step_mfu(ctx)
