"""One statement, one timeline (obs/trace.span, obs/profile.py): the
span tree of scan and join statements under ``trace_queries``, the same
spans as ``otb:`` events in a JAX profiler trace, the ledger columns
that decompose ``device_ms`` with tracing off, program names, join
modes as run, scopes that change no output, and the reduction on a
small recorded trace."""

import json
import os
import re
from contextlib import nullcontext

import pytest

from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.net.client import connect_tcp
from opentenbase_tpu.net.server import ClusterServer
from opentenbase_tpu.obs import profile
from opentenbase_tpu.obs import statements as stmtobs

Q6 = "select sum(p * q) from li where d >= 3 and d < 20 and q < 24"
Q1 = (
    "select f, sum(q), sum(p), count(*) from li where d <= 25 "
    "group by f order by f"
)
QJ = (
    "select o.c, sum(li.p) from li join o on li.k = o.k "
    "where li.d < 20 group by o.c order by o.c"
)
STATEMENTS = {"q6_like": Q6, "q1_like": Q1, "join": QJ}
SPLIT = ", ".join(stmtobs.DEVICE_SPLIT_FIELDS)


def _load(execute):
    execute(
        "create table li (k bigint, q bigint, p bigint, d bigint, f text) "
        "distribute by shard(k)"
    )
    execute("create table o (k bigint, c bigint) distribute by shard(k)")
    execute("insert into li values " + ",".join(
        f"({i},{i % 50},{100 + i % 7},{i % 30},'{'AB'[i % 2]}')"
        for i in range(400)
    ))
    execute("insert into o values " + ",".join(
        f"({i},{i % 5})" for i in range(0, 400, 2)
    ))
    execute("analyze")


@pytest.fixture(scope="module")
def wire():
    """A cluster behind its wire server with the three statements warm."""
    cluster = Cluster(num_datanodes=2, shard_groups=16)
    server = ClusterServer(cluster).start()
    client = connect_tcp(server.host, server.port)
    _load(client.execute)
    for q in STATEMENTS.values():
        client.execute(q)
    yield cluster, client
    client.close()
    server.stop()


def _traced(cluster, client, sql):
    client.execute("set trace_queries = on")
    try:
        client.execute(sql)
    finally:
        client.execute("set trace_queries = off")
    return next(
        tr for tr in reversed(cluster.tracer.last(4)) if tr.query == sql
    )


@pytest.mark.parametrize("kind", list(STATEMENTS))
def test_span_tree_under_trace_queries(wire, kind):
    cluster, client = wire
    tr = _traced(cluster, client, STATEMENTS[kind])
    by_id = {sp.span_id: sp for sp in tr.spans}
    assert len(by_id) == len(tr.spans), "every span has its own id"
    names = [sp.name for sp in tr.spans]
    root = tr.spans[0]
    assert root.name == "wire.request" and root.parent_id is None
    for must in (
        "wire.decode", "wire.lock_wait", "query", "parse", "plan",
        "execute", "fused", "fused.gate_wait", "fused.cache",
        "fused.bind", "fused.launch", "fused.wait", "fused.collect",
        "fused.merge", "wire.encode", "wire.send",
    ):
        assert must in names, (must, names)
    parent_of = {
        "query": "wire.request", "execute": "query", "fused": "execute",
        "fused.launch": "fused", "fused.wait": "fused",
        "fused.merge": "fused", "wire.send": "wire.request",
    }
    kids_us: dict = {}
    for sp in tr.spans[1:]:
        parent = by_id[sp.parent_id]  # one trace: every edge resolves
        if sp.name in parent_of:
            assert parent.name == parent_of[sp.name], (sp.name, parent.name)
        # every child inside its parent, on the one perf_counter clock
        assert sp.ts_us >= parent.ts_us - 1e-3, (sp.name, parent.name)
        assert (
            sp.ts_us + sp.dur_us <= parent.ts_us + parent.dur_us + 1e-3
        ), (sp.name, parent.name)
        kids_us[parent.span_id] = kids_us.get(parent.span_id, 0.0) + sp.dur_us
    # self times (a span less its children) sum to the root's duration
    selfs = [sp.dur_us - kids_us.get(sp.span_id, 0.0) for sp in tr.spans]
    assert min(selfs) >= -1e-3
    assert sum(selfs) == pytest.approx(root.dur_us, rel=1e-9)
    launch = next(sp for sp in tr.spans if sp.name == "fused.launch")
    assert re.match(r"^program_[a-z_]+$", launch.args["program"])
    assert root.args["rows"] >= 1 and root.args["bytes_out"] > 0
    if kind == "join":
        assert launch.args["join_modes"]


def test_bind_span_reports_the_lane_plan(wire):
    """A grouped statement on the XLA hash program: ``fused.bind`` says
    how many limb lanes the plan cut and how many the dtypes alone would
    have, the launch's ``mode`` carries the same K, and pg_stat_fused
    counts the launch as narrowed by the statistics."""
    cluster, client = wire
    # thirty groups (beyond the Pallas kernel's key domain), q < 50 and
    # p < 107 in one non-negative limb each, sum(q) and count(q) sharing
    sql = "select d, sum(q), count(q), sum(p) from li group by d order by d"

    def counters():
        rows = dict(
            client.execute("select event, detail from pg_stat_fused").rows
        )
        return int(rows["mxu_plans_bounded"]), int(rows["mxu_plans_full"])

    before = counters()
    tr = _traced(cluster, client, sql)
    bind = next(
        sp for sp in tr.spans
        if sp.name == "fused.bind" and "lanes" in sp.args
    )
    assert bind.args["program"] == "program_scan_xla_hash"
    # key d: 1 of 8 limbs; q: 1 of 8 (twice over: its sum, its count);
    # p: 1 of 8; ones
    assert bind.args["lanes"] == 4 and bind.args["lanes_full"] == 33
    launch = next(sp for sp in tr.spans if sp.name == "fused.launch")
    assert launch.args["mode"] == "hash/64/k4"
    after = counters()
    assert after[0] == before[0] + 1 and after[1] == before[1]


def test_radix_shape_rule_counter_and_bind_args(wire, monkeypatch):
    """``radix_sized_out`` advances once for a program whose join the
    estimates admitted to a radix table and the shape rule sent to
    sort-merge, never on a cached re-bind and not at all for a
    dimension-sized build; the program's ``fused.bind`` span (and the
    launch that traced it) carry the formulation with its widths."""
    from opentenbase_tpu.ops import pallas_join

    cluster, client = wire

    def sized_out():
        rows = dict(
            client.execute("select event, detail from pg_stat_fused").rows
        )
        return int(rows["radix_sized_out"]), rows["last_join_modes"]

    def spans(tr, name):
        return [sp for sp in tr.spans if sp.name == name]

    # o's 200 sparse keys against li's 400: not dense, so the fold's
    # flag hands the join to the radix gate (build * 2 <= probe)
    wide = ("select o.c, count(*) from li join o on li.k = o.k "
            "where li.d < 7 group by o.c order by o.c")
    small = ("select o.c, max(li.q) from li join o on li.k = o.k "
             "where li.d < 9 group by o.c order by o.c")
    before, _modes = sized_out()
    # every table is past a bound of zero partitions: what SF30's 2^21
    # customers a chip are to the real one (P <= 4096)
    monkeypatch.setattr(pallas_join, "MAX_PARTITIONS", 0)
    first = _traced(cluster, client, wide)
    after, modes = sized_out()
    assert after == before + 1 and modes == "merge"
    # the launch that traced the program says what the trace chose
    launch = spans(first, "fused.launch")[-1]
    assert launch.args["join_modes"] == "merge"
    # (``o.c``, a ``bigint`` of small values, crosses as one word; the
    # keys ``li.k`` and ``o.k`` fit too, so the sort-merge sorts one)
    assert re.match(r"^join0=merge:\d+x\d+ key=i32 narrow=c$",
                    launch.args["joins"])
    again = _traced(cluster, client, wide)
    assert sized_out() == (after, "merge")  # a cached re-bind
    bind = spans(again, "fused.bind")[-1]
    assert bind.args["cache"] == "hit"
    assert bind.args["join_modes"] == "merge"
    assert bind.args["joins"] == launch.args["joins"]
    build, probe = map(
        int, bind.args["joins"].split(":")[1].split()[0].split("x"))
    assert 0 < build < probe
    # flight 1's shape: a dimension-sized build keeps its table
    monkeypatch.undo()
    tr = _traced(cluster, client, small)
    assert sized_out() == (after, "radix")
    assert spans(tr, "fused.launch")[-1].args["joins"].startswith(
        "join0=radix:")


@pytest.fixture(scope="module")
def profiled(wire, tmp_path_factory):
    """The three statements under ``jax.profiler.start_trace`` with
    ``trace_queries`` on: (QueryTraces by kind, the loaded xplane)."""
    import jax

    cluster, client = wire
    out = str(tmp_path_factory.mktemp("xplane"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        traces = {
            k: _traced(cluster, client, q) for k, q in STATEMENTS.items()
        }
        # a ``wire.request`` span closes after its answer is sent, so
        # the client can be back here before the server thread has left
        # the last one, and stopping the profiler then loses that span
        # (one statement short: seen under six workers). The connection
        # is served in order: one more round trip, whose own span may be
        # the one cut, closes every span before it.
        client.execute("set trace_queries = off")
    finally:
        jax.profiler.stop_trace()
    return traces, profile.load(profile.find_xplane(out))


@pytest.mark.parametrize("kind", list(STATEMENTS))
def test_profiler_events_equal_the_query_trace(profiled, kind):
    traces, loaded = profiled
    tr = traces[kind]
    events = [
        ev for p in loaded["planes"] for line in p["lines"]
        for ev in line["events"]
        if ev[3].get("trace_id") == tr.trace_id
    ]
    got = {
        ev[3]["span_id"]: (ev[0], ev[3].get("parent_id")) for ev in events
    }
    # Tracer.finish gives the root no parent; its TraceMe names itself
    want = {
        sp.span_id: (
            "otb:" + sp.name,
            sp.parent_id or (sp.span_id if sp.name != "wire.request"
                             else tr.ctx.span_id),
        )
        for sp in tr.spans
    }
    assert got == want
    # and the reduction finds the statement with its class and counts
    report = profile.reduce(loaded)
    assert report["statements"] >= 3 + 2 * 3  # + the SET round trips
    classed = [
        c for k, c in report["classes"].items() if k != "unclassified"
    ]
    assert sum(c["launches"] for c in classed) >= 3
    assert all(
        c["spans"]["fused.launch"]["count"] == c["launches"]
        for c in classed if c["launches"]
    )
    # the join's launch says which formulation at which static widths
    joins = [j for c in classed for j in c["joins"]]
    assert len(joins) == 1 and re.match(
        r"^join0=(radix|merge|fold):\d+x\d+ narrow=c$", joins[0]), joins
    assert "joins ['join0=" in profile.render(report)
    # and its grouped final the formulation it holds, the capacity it
    # was compiled for and its keys
    assert [(c["grouping"], c["groups"], c["group_keys"]) for c in classed
            if c["groups"]] == [
        ({"direct/8192": 1}, {"8192": 1}, {"1 (0 text)": 1})]
    assert ("grouping ['direct/8192'], groups ['8192'] of group_keys "
            "['1 (0 text)']") in profile.render(report)


def test_ledger_columns_filled_with_tracing_off(wire):
    cluster, client = wire

    def read():
        return {
            r[0]: [float(x) for x in r[1:]] for r in client.execute(
                f"select query, calls, device_ms, {SPLIT}, merge_ms, "
                "device_launches, device_syncs, fused_retries "
                "from pg_stat_statements"
            ).rows
        }

    before = read()
    for q in STATEMENTS.values():
        client.execute(q)
    after = read()
    moved = 0
    for query, row in after.items():
        if " pg_stat_" in query or query not in before:
            continue
        d = [a - b for a, b in zip(row, before[query])]
        if d[0] != 1:
            continue
        moved += 1
        device_ms, six = d[1], d[2:8]
        launches, syncs = d[9], d[10]
        assert launches >= 1 and syncs >= 1, query
        assert all(x >= 0 for x in six), (query, six)
        # the six parts never count a millisecond twice
        assert sum(six) <= device_ms + 1e-6, (query, six, device_ms)
        assert d[8] >= 0  # merge_ms
    assert moved == 3
    phases = {
        r[0] for r in
        client.execute("select phase from pg_stat_query_phases").rows
    }
    assert "wire" in phases


def test_join_modes_same_cold_retraced_and_cached():
    """One join statement cold, with new literals (a re-bind of the
    cached program) and from the program cache reports one join_modes."""
    s = Cluster(num_datanodes=2, shard_groups=16).session()
    _load(s.execute)

    def modes():
        rows = dict(s.query("select event, detail from pg_stat_fused"))
        # a cold run may launch a program twice (a capacity retry)
        return (
            rows["last_join_modes"],
            set(rows["last_programs"].split(",")),
        )

    s.query(QJ)
    cold = modes()
    s.query(QJ.replace("< 20", "< 11"))
    rebound = modes()
    s.query(QJ)
    cached = modes()
    assert cold == rebound == cached
    assert cold[0] and all(p.startswith("program_dag_") for p in cold[1])
    lines = [r[0] for r in s.query("explain analyze " + QJ)]
    assert f"Fused join modes: {cold[0]}" in lines, lines


def test_every_compiled_program_is_named(wire):
    """The XLA module of a program is ``jit_<its name>``: every program
    the scan and DAG paths hold is ``program_<what>``, no hash, no
    literal (the benchmark's rooflines match ``^jit_program``)."""
    cluster, _client = wire
    fx = cluster._fused
    names = set()
    for entry in fx._programs.values():
        if entry:
            names.add(entry[0].__name__)
    for key, entry in fx._dag._programs.items():
        for prog in entry[:fx._dag._NPROGS.get(key[0], 1)]:
            names.add(prog.__name__)
            assert isinstance(prog.join_modes, set)
    assert names
    for n in names:
        assert re.match(r"^jit_program_[a-z_]+$", "jit_" + n), n
    prog = next(iter(fx._dag._programs.values()))[0]
    assert prog.__name__.startswith("program_dag_")


def test_scopes_change_no_output(monkeypatch):
    """``jax.named_scope`` is metadata: the three statements answer the
    same with every ``otb/`` scope taken out."""
    def answers():
        s = Cluster(num_datanodes=2, shard_groups=16).session()
        _load(s.execute)
        return [s.query(q) for q in STATEMENTS.values()]

    with_scopes = answers()
    from opentenbase_tpu.executor import fused, fused_dag
    from opentenbase_tpu.ops import agg, pallas_join, pallas_scan

    for mod in (fused, fused_dag, agg, pallas_scan, pallas_join):
        monkeypatch.setattr(mod, "scope", lambda stage: nullcontext())
    assert answers() == with_scopes


def test_explain_analyze_reports_fragments_from_spans():
    s = Cluster(num_datanodes=2, shard_groups=16).session()
    _load(s.execute)
    s.query(QJ)
    lines = [r[0] for r in s.query("explain (analyze, verbose) " + QJ)]
    frag = [ln for ln in lines if ln.strip().startswith("device fragment")]
    assert frag and "final" in frag[-1], lines
    fused_line = next(ln for ln in lines if ln.startswith("  fused: "))
    assert "device_launches=" in fused_line and "bind=" in fused_line


def test_scope_of_reads_the_stage_words():
    f = profile.scope_of
    assert f("jit(program_dag_gsort)/shard_map/otb/join1/merge/sort/sort:") \
        == "join1/merge/sort"
    assert f("jit(p)/otb/agg/limbs/while/body/closed_call/shift:") \
        == "agg/limbs"
    assert f("jit(p)/otb/join0/pallas/probe/otb/kernel/pallas_call/x") \
        == "join0/pallas/probe/kernel"
    assert f("jit(p)/otb/scan/decode/jit(_where)/select_n") == "scan/decode"
    assert f("reduce_window_sum:") == "" and f(None) == ""


@pytest.fixture(scope="module")
def recorded():
    """tests/data/profile_trace.json and its reduction: three statements
    on one chip. Class 77, statement 1 runs one program with an idle gap
    between two of its ops (inside the module); statement 2 runs two
    programs with a gap between them while the host sits in
    ``fused.wait``, and a gap after the last one while no inner span is
    open; a fourth program runs outside any statement. Class 88 is one
    statement of three programs whose ops are the cases of the stage
    inheritance (lines a v5e trace holds, cut to toy widths; the third
    program comes with its HLO module)."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "profile_trace.json")) as f:
        trace = json.load(f)
    return trace, profile.reduce(trace)


def test_profile_reduction_on_a_recorded_trace(recorded):
    _trace, report = recorded
    assert report["statements"] == 3
    assert sorted(report["classes"]) == ["77", "88"]
    c = report["classes"]["77"]
    assert (c["launches"], c["syncs"], c["retries"]) == (3, 3, 1)
    assert c["join_modes"] == {"merge": 1, "fold+merge": 1}
    assert c["programs_ms"] == pytest.approx({
        "jit_program_scan_pallas": 6.0, "jit_program_dag_count": 1.0,
        "jit_program_dag_gsort": 4.0,
    })
    # op self time: the while keeps what its body does not cover; the
    # DIRECT time by scope is what it was before ops took stages from
    # the graph, to the last digit
    assert c["scopes_ms"] == {
        "scan/decode": 2.0, "scan/kernel": 2.0, "agg/onehot": 0.5,
        "(no scope)": 1.0, "exchange/count": 0.5,
        "join0/merge/sort": 4.0,
    }
    assert c["device_busy_ms"] == 10.0
    # their lines end in ``(...)``: nothing to read, nothing placed
    assert c["unscoped_ops_ms"] == {"%while.1": 0.5, "%copy.9": 0.5}
    assert c["scopes_inherited_ms"] == {} and c["inherited_ops"] == []
    assert c["unscoped_ms"] == 1.0 and c["unscoped_pct"] == 10.0
    # each gap split over the innermost spans open across it; the part
    # a running program covers is the program's
    assert c["idle_ms"] == pytest.approx({
        "in_program:jit_program_scan_pallas": 1.0,  # inside a module
        "fused.wait": 0.5 + 1.7 + 2.5,  # between modules: host waiting
        "fused.bind": 1.0 + 1.0 + 0.8,
        "fused.cache": 0.5,
        "fused.launch": 0.5 + 0.5 + 0.5,
        "fused.collect": 0.4 + 0.5,
        "fused": 0.1 + 0.5 + 0.3,
        "query": 0.3 + 0.3 + 0.3 + 0.2,
        "wire.request": 0.2 + 0.2 + 0.2 + 6.0,  # no inner span open
    })
    assert sum(c["idle_ms"].values()) == pytest.approx(20.0)
    spans = c["spans"]
    assert spans["wire.request"]["count"] == 2
    assert spans["fused.bind"]["count"] == 3
    assert spans["fused.bind"]["total_ms"] == pytest.approx(3.3)
    assert spans["fused.bind"]["self_ms"] == pytest.approx(3.3 - 0.5)
    assert c["dispatch_split_ms"]["fused.cache"] == pytest.approx(0.5)
    assert c["dispatch_split_sum_ms"] == pytest.approx(
        2.8 + 0.5 + 3.0 + 14.2 + 0.9
    )
    assert report["outside"]["programs_ms"] == pytest.approx(
        {"jit_program_warmup": 2.0}
    )
    assert report["outside"]["device_busy_ms"] == pytest.approx(2.0)
    text = profile.render(report)
    assert "in_program:jit_program_scan_pallas" in text
    assert "ops under no scope" in text


#: op -> (the stage it takes, the op it takes it from, how), by program,
#: in class 88 of the recorded trace; None: the graph cannot place it
STAGE_CASES = {
    "one_scoped_reader": (
        "jit_program_dag_gsort", "%copy.7",
        ("scan/kernel", "%kernel.1", "reader")),
    # X64SplitLow -> bitcast -> relayout copy -> bitcast -> decode, as a
    # Q6 has it: the copy first, the split a round later
    "chain_of_two_relayout": (
        "jit_program_dag_gsort", "%copy.1",
        ("scan/decode", "%fusion.2", "reader")),
    "chain_of_two_split": (
        "jit_program_dag_gsort", "%custom-call.1",
        ("scan/decode", "%copy.1", "reader")),
    # %fusion.10 (scan/predicate) starts before %fusion.9 (scan/mvcc)
    "readers_in_two_scopes_first_to_start_wins": (
        "jit_program_dag_gsort", "%custom-call.8",
        ("scan/predicate", "%fusion.10", "reader")),
    "no_reader_a_scoped_producer": (
        "jit_program_dag_gsort", "%copy.20",
        ("scan/kernel", "%kernel.1", "producer")),
    "neither_stays_unscoped": ("jit_program_dag_gsort", "%iota.3", None),
    # the other program's %copy.1 is another op
    "same_op_name_in_two_modules": (
        "jit_program_dag_grouped", "%copy.1",
        ("final/grouped/pack", "%fusion.12", "reader")),
    "op_in_a_while_body": (
        "jit_program_dag_gsort", "%dynamic-update-slice.47",
        ("final/gsort/topk", "%while.25", "producer")),
    # its readers name get-tuple-elements: bound by shape and order
    "tuple_shaped_result": (
        "jit_program_dag_gsort", "%reduce-window.2",
        ("join1/merge/prefix", "%broadcast_select_fusion.2", "reader")),
    "text_the_parser_does_not_know": (
        "jit_program_dag_gsort", "%mystery.5", None),
    "no_hlo_line_at_all": (
        "jit_program_dag_gsort", "no HLO line at all", None),
    # the trace holds program 903's module: a fusion from inside it ...
    "module_fusion_from_inside": (
        "jit_program_probe", "%fusion.1",
        ("scan/decode", "%fusion.1", "inside")),
    # ... and a reader found through the module's own bitcast
    "module_reader_through_a_bitcast": (
        "jit_program_probe", "%custom-call.2",
        ("join0/fold/probe", "%fusion", "reader")),
}


@pytest.mark.parametrize("case", list(STAGE_CASES))
def test_an_op_without_a_scope_takes_its_stage_from_the_graph(
        recorded, case):
    _trace, report = recorded
    program, op, want = STAGE_CASES[case]
    c = report["classes"]["88"]
    got = [r for r in c["inherited_ops"]
           if (r["program"], r["op"]) == (program, op)]
    if want is None:
        assert got == [] and c["unscoped_ops_ms"][op] > 0
        return
    (row,) = got  # listed once
    assert (row["scope"], row["from"], row["how"]) == want
    assert op not in c["unscoped_ops_ms"] or case.startswith("same_op")
    text = profile.render(report)
    assert f"{op:<28} <- {want[1]:<28} {want[0]}" in text


def test_direct_inherited_and_unscoped_add_up_to_the_self_time(recorded):
    """Per class: the scoped part of ``scopes_ms`` + what the graph
    placed + what it could not = the device ops' self time, a ``while``
    counted less its body; ``scope_rates`` holds the same total."""
    _trace, report = recorded
    for c in report["classes"].values():
        direct = sum(v for k, v in c["scopes_ms"].items()
                     if k != "(no scope)")
        placed = sum(c["scopes_inherited_ms"].values())
        assert placed == pytest.approx(
            sum(r["ms"] for r in c["inherited_ops"]))
        assert direct + placed + c["unscoped_ms"] == pytest.approx(
            c["device_busy_ms"])
        assert c["scopes_ms"].get("(no scope)", 0.0) == pytest.approx(
            placed + c["unscoped_ms"])
        assert sum(r["ms"] for r in c["scope_rates"].values()) \
            == pytest.approx(c["device_busy_ms"])
    c = report["classes"]["88"]
    assert c["device_busy_ms"] == pytest.approx(30.0)
    # %while.25 ran 4 ms round a body of 2
    (loop,) = [r for r in c["ops"] if r["op"] == "%while.25"]
    assert loop["ms"] == pytest.approx(2.0) and loop["bytes"] == 0
    assert c["scopes_inherited_ms"] == pytest.approx({
        "scan/decode": 3.0, "scan/kernel": 2.0, "scan/predicate": 1.0,
        "join1/merge/prefix": 3.0, "final/gsort/topk": 1.0,
        "final/grouped/pack": 1.0, "join0/fold/probe": 1.0,
    })
    assert c["unscoped_ms"] == pytest.approx(2.0)
    assert c["unscoped_pct"] == pytest.approx(100.0 * 2.0 / 30.0)


def test_costliest_ops_say_what_each_read_from_where(recorded):
    """The table's rows: elements, ns an element, bytes (the compiler's
    count where the event has one, else by the shapes), operands with
    their memory space, and by scope the gathers whose table lies
    outside ``S(1)``."""
    _trace, report = recorded
    c = report["classes"]["88"]
    rows = {(r["program"], r["op"]): r for r in c["ops"]}
    probe = rows["jit_program_dag_grouped", "%fusion.2"]
    assert probe["scope"] == "join1/fold/probe"
    assert probe["opcode"] == "fusion:kCustom"
    assert probe["result"] == ["pred[1024]@hbm"]
    assert probe["operands"] == ["pred[64]@S(1)", "s32[1024]@hbm"]
    assert probe["tables"] == [0] and probe["elements"] == 1024
    assert probe["ns_per_element"] == pytest.approx(2e6 / 1024)
    assert probe["bytes"] == 1024 + 64 + 4 * 1024  # by the shapes
    assert probe["gb_per_s"] == pytest.approx(5184 / 2e6)
    # through HBM: the result and the indices; the table lies on chip
    assert probe["hbm_peak_pct"] == pytest.approx(
        100 * (1024 + 4 * 1024) / 2e6 / 819.1576375296)
    gather = rows["jit_program_dag_grouped", "%fusion.9"]
    assert gather["operands"][0] == "s32[64]@hbm"
    rates = c["scope_rates"]
    assert rates["join1/fold/probe"]["operands_outside_s1"] == {
        "ops": 0, "ms": 0}
    assert rates["join2/fold/gather"]["operands_outside_s1"] == {
        "ops": 1, "ms": pytest.approx(3.0)}
    # the event's own count wins over the shapes (3 results + 3 operands
    # of 640 x 128 would be 1,884,160 B)
    prefix = rows["jit_program_dag_gsort", "%broadcast_select_fusion.2"]
    assert prefix["bytes"] == 1310720
    scan = rows["jit_program_dag_gsort", "%reduce-window.2"]
    assert scan["result"] == ["u32[640,128]@hbm"] * 2
    assert scan["inherited_from"] == "%broadcast_select_fusion.2"
    assert rates["join1/merge/prefix"]["bytes"] == 1310720 + 1310728
    # with the module: what the fusion's callee holds, its table by the
    # gather inside; on-chip operands alone load HBM with nothing
    held = rows["jit_program_probe", "%fusion"]
    assert held["opcode"] == "fusion:kCustom(gather,transpose)"
    assert held["operands"] == ["s32[1048576]@S(1)", "s32[4194304]@S(1)"]
    both = rates["join0/fold/probe"]  # with %custom-call.2: S(1) to S(1)
    assert both["bytes"] == held["bytes"] + 4 * (4194304 + 1048576)
    assert both["hbm_peak_pct"] == pytest.approx(  # %fusion's result alone
        100 * 4 * 4194304 / 3e6 / 819.1576375296)
    text = profile.render(report)
    assert "<- *pred[64]@S(1), s32[1024]@hbm" in text
    assert "1 outside S(1), 3.000 ms" in text
    json.dumps(report)  # --json prints it


REAL_LINES = {
    # the one-chip Q3's gathers (ledger, PR 36, ``_fusion.6___pred_2097152
    # __0:T_1024__128__4_1_S_1___fusion_pred_2...``)
    "gather_table_in_s1": (
        "%fusion.7 = pred[16777216]{0:T(1024)(128)(4,1)} fusion("
        "pred[2097152]{0:T(1024)(128)(4,1)S(1)} %fusion.6, "
        "s32[16777216]{0:T(1024)} %broadcast_clamp_fusion), kind=kCustom, "
        "calls=%fused_computation.7",
        ("fusion.7", "fusion", "kCustom", [["pred", [16777216], 0]],
         [("fusion.6", [["pred", [2097152], 1]]),
          ("broadcast_clamp_fusion", [["s32", [16777216], 0]])])),
    "result_in_s1": (
        "%fusion.6 = pred[2097152]{0:T(1024)(128)(4,1)S(1)} fusion("
        "pred[2097152]{0:T(1024)(128)(4,1)S(1)} %reshape.314, "
        "s32[2097152]{0:T(1024)S(1)} %broadcast_clamp_fusion.1), "
        "kind=kCustom, calls=%fused_computation.6",
        ("fusion.6", "fusion", "kCustom", [["pred", [2097152], 1]],
         [("reshape.314", [["pred", [2097152], 1]]),
          ("broadcast_clamp_fusion.1", [["s32", [2097152], 1]])])),
    "tuple_shaped_reduce_window": (
        "%reduce-window.4 = (u32[655360,128]{0,1:T(8,128)}, "
        "u32[655360,128]{0,1:T(8,128)}) reduce-window("
        "u32[655360,128]{0,1:T(8,128)} %get-tuple-element.1365, "
        "u32[655360,128]{0,1:T(8,128)} %get-tuple-element.1226, "
        "u32[]{:T(128)} %constant.125, u32[]{:T(128)} %constant.95), "
        "window={size=1x128 pad=0_0x0_127}, to_apply=%region_15.30.clone",
        ("reduce-window.4", "reduce-window", "",
         [["u32", [655360, 128], 0]] * 2,
         [("get-tuple-element.1365", [["u32", [655360, 128], 0]]),
          ("get-tuple-element.1226", [["u32", [655360, 128], 0]]),
          ("constant.125", [["u32", [], 0]]),
          ("constant.95", [["u32", [], 0]])])),
    "x64_split_custom_call": (
        "%custom-call.12 = u32[2,33554432]{1,0:T(2,128)} custom-call("
        "s64[2,33554432]{1,0:T(2,128)} %xmax.1), "
        'custom_call_target="X64SplitHigh", sharding={replicated}, '
        'frontend_attributes={xla.sdy.sharding="#sdy.sharding<@mesh, '
        '[{\\"dn\\"}, {}]>"}',
        ("custom-call.12", "custom-call", "X64SplitHigh",
         [["u32", [2, 33554432], 0]],
         [("xmax.1", [["s64", [2, 33554432], 0]])])),
    "tuple_operand_of_an_async_done": (
        "%slice-done.1 = pred[16777216]{0:T(1024)(128)(4,1)S(1)} "
        "async-done(((pred[67108864]{0:T(1024)(128)(4,1)}), "
        "pred[16777216]{0:T(1024)(128)(4,1)S(1)}, s32[]{:S(2)}) "
        "%slice-start.1)",
        ("slice-done.1", "async-done", "", [["pred", [16777216], 1]],
         [("slice-start.1", [["pred", [67108864], 0],
                             ["pred", [16777216], 1], ["s32", [], 2]])])),
    "index_comments_in_a_long_tuple": (
        "%broadcast_select_fusion.2 = (u32[640,128]{0,1:T(8,128)}, "
        "u32[640,128]{0,1:T(8,128)}, /*index=2*/u32[640,128]{0,1:T(8,128)})"
        " fusion(pred[640,128]{0,1:T(8,128)(4,1)S(1)} %copy-done.14), "
        "kind=kLoop, calls=%fused_computation.2135",
        ("broadcast_select_fusion.2", "fusion", "kLoop",
         [["u32", [640, 128], 0]] * 3,
         [("copy-done.14", [["pred", [640, 128], 1]])])),
    "operands_elided": ("%while.1 = (s32[], f32[8]{0}) while(...)", None),
    "no_instruction": ("ThreadpoolListener::Region", None),
    "cut_short": ("%x = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b", None),
    "empty": ("", None),
}


@pytest.mark.parametrize("case", list(REAL_LINES))
def test_hlo_line_reads_what_a_v5e_trace_holds(case):
    """An op event's name on a v5e is its whole HLO line (my chip run,
    PR 37, ``chiprun_out/pr37/*.xplane.pb``); text that is none gives
    None and never raises."""
    text, want = REAL_LINES[case]
    got = profile.hlo_line(text)
    if want is None:
        assert got is None
        return
    name, rec, stubs = got
    shapes = [leaf[:3] for leaf in rec["shapes"]]
    operands = [
        (o, [leaf[:3] for leaf in stubs[o]]) for o in rec["operands"]
    ]
    assert (name, rec["op"], rec["kind"], shapes, operands) == want
    # the layout's text is kept whole: what binds a tuple's element
    assert all(leaf[3].startswith("{") for leaf in rec["shapes"])


def test_hlo_module_of_a_compiled_program():
    """Where the trace holds a program's HLO module (a v5e keeps it for
    a program the traced process compiled, not for one loaded from the
    compile cache), ``load`` decodes it: here the CPU backend's, which
    the profiler stores the same way."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def program_probe(x, i):
        with jax.named_scope("otb/scan/decode"):
            y = x * 2 + 1
        with jax.named_scope("otb/join0/fold/probe"):
            return jnp.take(y, i, mode="clip")

    compiled = program_probe.lower(
        jnp.arange(64, dtype=jnp.int32), jnp.arange(8, dtype=jnp.int32)
    ).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    blob = module.as_serialized_hlo_module_proto()
    size, head = len(blob), bytearray(b"\x0a")  # HloProto.hlo_module = 1
    while True:
        head.append((size & 0x7F) | (0x80 if size >> 7 else 0))
        size >>= 7
        if not size:
            break
    instrs = profile.hlo_module(memoryview(bytes(head) + blob))
    text = module.to_string()
    entry = text[text.index("ENTRY"):]
    names = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", entry, re.M)
    assert names and set(names) <= set(instrs)
    params = [i for i in instrs.values() if i["op"] == "parameter"]
    assert {(64,), (8,)} <= {tuple(p["shapes"][0][1]) for p in params}
    scopes = {i["scope"] for i in instrs.values()} \
        | {s for i in instrs.values() for s in i.get("inner", ())}
    assert {"scan/decode", "join0/fold/probe"} <= scopes
    gathers = [i for i in instrs.values() if i.get("tables")]
    assert gathers, "the gather names the operand it reads from"
    for i in instrs.values():
        assert set(i["operands"]) <= set(instrs)


def test_ids_never_read_as_numbers():
    """A span or trace id rides the profiler's TraceMe as metadata, and
    a value that parses as a number is stored as one (an all-digit id
    came back an int and left its trace): every id starts with a hex
    letter, so neither ``int`` nor ``float`` takes it."""
    from opentenbase_tpu.obs.tracectx import new_span_id, new_trace_id

    for make, n in ((new_span_id, 16), (new_trace_id, 32)):
        for _ in range(2000):
            i = make()
            assert len(i) == n and i[0] in "abef", i
            int(i, 16)
            for parse in (int, float):
                with pytest.raises(ValueError):
                    parse(i)
