"""Device time inside the catch-up's replay loop (the outermost loop of
``_catchup_impl``, body ``catchup.round``) per replay round, in ms: the
loop's device time over the rounds the device ran, its body's iterations
(``bench/program_spans.py``).  ``rounds`` and ``calls``: the window's
rounds and catch-up calls; ``scopes``: ms per round by named scope, where
xprof can read the trace."""
import program_spans


def read(ctx):
    hidden = ctx["sz"]["hidden_size"]
    r = program_spans.load()
    value = program_spans.ms_per_round(r, hidden)
    if value is None:
        return None
    c = program_spans.counts(r, hidden)
    extra = {"rounds": c["rounds"], "calls": c["calls"]}
    scopes = program_spans.scope_ms(r, hidden)
    if scopes:
        extra["scopes"] = scopes["round"]
    return value, extra
