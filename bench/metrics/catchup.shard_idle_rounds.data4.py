"""Share of the rounds a chip's replay loop ran in which none of the rows
it holds replayed a token, in %: the loop runs to the longest backlog of
all the mesh's rows, so a chip also runs the rounds the other chips' rows
need (``bench/program_spans.py`` ``shard_idle_rounds``).  ``rounds`` and
``needed``: the rounds a chip ran a call and those its own rows needed,
each a mean over calls and chips."""
import program_spans


def read(ctx):
    return program_spans.shard_idle_rounds(
        program_spans.load(), ctx["sz"]["hidden_size"], ctx["steps"])
