"""The program-span readers (``program_spans.py`` and the metrics that use
it) on a small trace recorded on a TPU v5e, ``testdata/catchup.xplane.pb``:
six steps of a tiny ``granite-8b.storm`` session (4 streams x 24 tokens, 2
layers of width 64) driven by ``harness.run_cell(..., trace=True,
max_steps=6)``, so the session's spans lie on the host plane inside
``bench.window``.  The recording was trimmed to what the readers read: the
TPU plane's ``XLA Modules`` and ``XLA Ops`` lines, each operation's name cut
to its name and output shape (``trace_reduce.op_name``), and the Python
thread of the host plane; the programs' HLO went with the rest, so xprof
finds no named scope in it.  ``testdata/tiny.xplane.pb`` has no program
spans."""
import os

import pytest

import harness
import program_spans
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CATCHUP = os.path.join(HERE, "testdata", "catchup.xplane.pb")
TINY = os.path.join(HERE, "testdata", "tiny.xplane.pb")
READERS = ["catchup.ms_per_round.storm", "catchup.ms_per_round.sparse",
           "catchup.fixed_ms_per_call.storm",
           "catchup.fixed_ms_per_call.sparse",
           "catchup.useful_row_share.storm",
           "catchup.useful_row_share.sparse",
           "session.sync_idle_ms_per_step.storm",
           "session.host_idle_ms_per_step.storm"]


HIDDEN = 64        # the recorded session's hidden size
CTX = {"sz": {"hidden_size": HIDDEN}}


@pytest.fixture(scope="module")
def r():
    return program_spans.load(CATCHUP)


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(trace_reduce.load(CATCHUP))


def reader(metric):
    return harness.load_module(os.path.join(HERE, "metrics", f"{metric}.py"),
                               "test_metric_" + metric.replace(".", "_"))


def test_device_counts_agree_with_the_spans(r):
    """Each run's replay loop iterates as many rounds, over as many rows,
    as its edge.catchup span reports."""
    assert r["steps"] >= 3
    assert len(r["calls"]) == r["runs"] == len(r["catchups"]) >= 2
    for call, span in zip(r["calls"], r["catchups"]):
        assert call["rounds"] == span["rounds"] >= 1
        assert program_spans.rows(call["shapes"], HIDDEN) == span["rows"] == 4
        assert span["rounds"] <= span["replayed"] <= span["rounds"] * 4
    assert any(c["rounds"] > 1 for c in r["calls"])
    c = program_spans.counts(r, HIDDEN)
    assert c["calls"] == len(r["calls"])
    assert c["rounds"] == sum(a["rounds"] for a in r["catchups"])
    assert c["rows_decoded"] == 4 * c["rounds"]
    assert c["replayed"] == sum(a["replayed"] for a in r["catchups"])


@pytest.mark.parametrize("arg,delta", [("rounds", 1), ("rounds", -1),
                                       ("rows", 1)])
def test_a_span_that_disagrees_with_the_device_reads_nothing(r, arg, delta):
    """The span args are a cross-check: a report off by one in one call
    makes every catch-up reader read None, whatever the rest says."""
    spans = [dict(a) for a in r["catchups"]]
    spans[1][arg] += delta
    bad = dict(r, catchups=spans)
    assert program_spans.counts(bad, HIDDEN) is None
    for f in (program_spans.ms_per_round, program_spans.fixed_ms_per_call,
              program_spans.useful_row_share):
        assert f(bad, HIDDEN) is None
    assert program_spans.counts(dict(r, catchups=spans[:-1]), HIDDEN) is None


def test_rows_need_the_hidden_size(r):
    """Rows are read from the [rows, hidden] operations of the round: at
    another hidden size there are none, and the counts disagree."""
    assert all(program_spans.rows(c["shapes"], HIDDEN + 1) == 0
               for c in r["calls"])
    assert program_spans.counts(r, HIDDEN + 1) is None


def test_rows_are_read_from_the_round_not_its_layers():
    """A round of 2 iterations over 32 rows whose nested layer loop holds
    more ``[1, hidden]`` weight slices than the round holds ``[32, hidden]``
    states (as granite-8b's does on the v5e): the rows are the round's."""
    ops, t = [], 0
    run = (0, 1000, "1")
    ops.append((10, 900, "%while.1 ="))
    for _ in range(2):                       # two rounds
        ops.append((t + 20, t + 21, "%fusion.1 = bf16[32,4096]"))
        ops.append((t + 22, t + 400, "%while.2 ="))         # the layers
        for k in range(5):
            ops.append((t + 30 + k, t + 31 + k, "%fusion.9 = f32[1,4096]"))
        ops.append((t + 401, t + 402, "%select.3 = f32[32,4096]"))
        t += 420
    ((_, _, direct, shapes),), inside, outside = program_spans._loops(
        ops, [run])
    assert program_spans._iterations(direct) == 2
    assert program_spans.rows(shapes, 4096) == 32
    assert inside[("1", "%fusion.9")] == 10 and not outside


def test_iterations_are_the_count_most_body_operations_share():
    from collections import Counter
    body = Counter({"%a": 3, "%b": 3, "%c": 3, "%cond": 4})
    assert program_spans._iterations(body) == 3
    assert program_spans._iterations(Counter({"%a": 2, "%b": 3})) == 2
    assert program_spans._iterations(Counter()) == 0


def test_round_and_call_split_sums_to_the_program_time(r, summary):
    c = program_spans.counts(r, HIDDEN)
    split = (program_spans.ms_per_round(r, HIDDEN) * c["rounds"]
             + program_spans.fixed_ms_per_call(r, HIDDEN) * c["calls"])
    assert split == pytest.approx(summary["programs"]["_catchup_impl"] * 1e3,
                                  rel=1e-9)
    assert 0 < r["loop_s"] < r["catchup_s"]


def test_useful_row_share(r):
    c = program_spans.counts(r, HIDDEN)
    assert program_spans.useful_row_share(r, HIDDEN) == pytest.approx(
        100.0 * c["replayed"] / c["rows_decoded"])
    assert 0 < program_spans.useful_row_share(r, HIDDEN) <= 100


def test_idle_by_span_within_the_idle_time(r, summary):
    idle = summary["window_s"] - summary["busy_s"]
    assert r["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert sum(r["idle_by_span"].values()) <= idle * (1 + 1e-9)
    sync, by_sync = program_spans.sync_idle_ms_per_step(r)
    host, by_host = program_spans.host_idle_ms_per_step(r)
    assert sync > 0 and host > 0
    assert set(by_sync) <= set(program_spans.READBACK)
    assert not set(by_sync) & set(by_host)
    assert (sync + host) * r["steps"] * 1e-3 <= idle * (1 + 1e-9)


def test_scopes_need_the_programs_hlo(r):
    """The named-scope split comes from xprof's op statistics, which need
    the programs' HLO (a full trace carries it; the trimmed one does not),
    and xprof reads a copy: nothing is written beside the trace."""
    pytest.importorskip("xprof")
    before = sorted(os.listdir(os.path.dirname(CATCHUP)))
    assert program_spans.scope_ms(r, HIDDEN) is None
    assert sorted(os.listdir(os.path.dirname(CATCHUP))) == before


@pytest.mark.parametrize("expr,part", [
    ("%convert.3 = bf16[5,64,64]{2,1,0:T(8,128)(2,1)} "
     "convert(f32[5,64,64]{2,1,0:T(8,128)} %p)", "f32 -> bf16"),
    ("%copy.23 = bf16[256,64]{1,0:T(8,128)(2,1)S(1)} "
     "copy(f32[256,64]{0,1:T(8,128)S(1)} %copy-done.9)", "f32 -> bf16"),
    ("%copy.7 = bf16[2,4,24,2,16]{2,4,3,1,0} "
     "copy(bf16[2,4,24,2,16]{2,4,3,1,0} %p)", "copies"),
    ("%copy-done.2 = bf16[2,4]{1,0} copy-done((bf16[2,4]{1,0}) %s)",
     "copies"),
    ("%select_reduce_fusion = s32[]{:T(128)} fusion(pred[4]{0} %a)", "rest"),
])
def test_fixed_parts_outside_the_scopes(expr, part):
    assert program_spans._part("fixed", "", expr) == part


@pytest.mark.parametrize("name,want", [
    ("jit(_catchup_impl)/while/body/catchup.round/jit(step_at)/"
     "decode.merge/select_n", ("round", "decode.merge")),
    ("jit(_catchup_impl)/while/body/catchup.round/gather",
     ("round", "catchup.round (rest)")),
    ("jit(_catchup_impl)/catchup.v_head/reduce_sum",
     ("fixed", "catchup.v_head")),
    ("jit(_catchup_impl)/catchup.combine/pallas_call",
     ("fixed", "catchup.combine")),
])
def test_parts_by_named_scope(name, want):
    kind, part = want
    assert program_spans._part(kind, name, "%x = f32[4] add()") == part


def test_each_split_sums_to_its_metric(r, monkeypatch):
    """With every operation of the recorded program given a scope (the
    trimmed trace has no HLO for xprof to read), the parts of a round and
    of a call sum to ``ms_per_round`` and ``fixed_ms_per_call``."""
    ops = {key: ("jit(_catchup_impl)/while/body/catchup.round/"
                 + ("decode.merge/select_n" if i % 2 else "dot_general"),
                 "%x = f32[4] add()")
           for i, key in enumerate(r["loop_ops"])}
    ops.update({key: ("", "%copy.1 = f32[4] copy(f32[4] %p)")
                for key in r["fixed_ops"]})
    monkeypatch.setattr(program_spans, "_op_stats", lambda path: ops)
    by = program_spans.scope_ms(r, HIDDEN)
    assert set(by["round"]) == {"decode.merge", "catchup.round (rest)",
                                "(no operation)"}
    assert set(by["fixed"]) == {"copies", "(no operation)"}
    assert sum(by["round"].values()) == pytest.approx(
        program_spans.ms_per_round(r, HIDDEN), rel=1e-9)
    assert sum(by["fixed"].values()) == pytest.approx(
        program_spans.fixed_ms_per_call(r, HIDDEN), rel=1e-9)
    assert all(v >= 0 for part in by.values() for v in part.values())
    del ops[next(iter(r["fixed_ops"]))]           # an operation xprof lacks
    assert program_spans.scope_ms(r, HIDDEN) is None


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_the_recorded_run(metric, monkeypatch):
    monkeypatch.setattr(harness, "find_xplane", lambda: CATCHUP)
    got = reader(metric).read(CTX)
    value = got[0] if isinstance(got, tuple) else got
    assert value > 0


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_without_program_spans(metric, monkeypatch):
    monkeypatch.setattr(harness, "find_xplane", lambda: TINY)
    assert reader(metric).read(CTX) is None
