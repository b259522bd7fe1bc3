"""Device-idle time per step while the host blocked on a readback (the
innermost program span open was ``edge.trigger`` or ``catchup.wait``), in
ms; ``by_span``: the same by span (``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    got = program_spans.sync_idle_ms_per_step(program_spans.load())
    return None if got is None else (got[0], {"by_span": got[1]})
