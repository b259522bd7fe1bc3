"""Device-idle time per step inside ``session.step`` under no readback
span (dispatch, argument upload, policy, bookkeeping), in ms; ``by_span``:
the same by the innermost span open (``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    got = program_spans.host_idle_ms_per_step(program_spans.load())
    return None if got is None else (got[0], {"by_span": got[1]})
