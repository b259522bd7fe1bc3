"""Device time of ``_catchup_impl`` outside its replay loop per call, in
ms: the weight converts, the v head and the combine, over the window's
catch-up calls (``bench/program_spans.py``).  ``scopes``: ms per call by
part (``catchup.v_head``, ``catchup.combine``, converts, copies, the
rest), where xprof can read the trace."""
import program_spans


def read(ctx):
    hidden = ctx["sz"]["hidden_size"]
    r = program_spans.load()
    value = program_spans.fixed_ms_per_call(r, hidden)
    if value is None:
        return None
    scopes = program_spans.scope_ms(r, hidden)
    return value, ({"scopes": scopes["fixed"]} if scopes else {})
