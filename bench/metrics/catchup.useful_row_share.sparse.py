"""Rows of the catch-up's replay rounds that replayed a backlog token, in
%: the ``replayed`` tokens of the window's ``edge.catchup`` spans over the
rows the device decoded, each call's loop iterations x the rows of its
round (``bench/program_spans.py``)."""
import program_spans


def read(ctx):
    return program_spans.useful_row_share(program_spans.load(),
                                          ctx["sz"]["hidden_size"])
