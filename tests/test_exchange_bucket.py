"""The mesh's redistribute fills its ``(D, cap)`` slabs with one
payload-carrying sort and D contiguous slices a column
(executor/fused_dag.py ``_compile_exchange``): what every device
receives from ``program_dag_exchange`` on four virtual devices against a
numpy reference of stable bucketing, and the lowered programs' structure
(one sort under ``exchange/bucket``, no scatter, no search loop)."""

import re

import numpy as np
import pytest

from opentenbase_tpu import types as ty
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.executor import fused_dag
from opentenbase_tpu.storage.column import Column

D = 4
NAMES = ("x", "f", "a", "b")  # the exchanged schema, in order


def bucket_reference(dest, live, cols, valids, cap):
    """Stable bucketing of one device's rows, in numpy alone: bucket
    ``d`` holds the live rows bound for ``d`` in their source order.
    Returns (counts [D], {name: [D, cap]} data, the same of validity);
    slots at or beyond a bucket's count are not defined."""
    counts = np.zeros(D, dtype=np.int64)
    data = {k: np.zeros((D, cap), dtype=v.dtype) for k, v in cols.items()}
    valid = {k: np.zeros((D, cap), dtype=bool) for k in cols}
    for r in range(len(dest)):
        if not live[r]:
            continue
        d, slot = dest[r], counts[dest[r]]
        for k in cols:
            data[k][d, slot] = cols[k][r]
            valid[k][d, slot] = valids[k][r]
        counts[d] += 1
    return counts, data, valid


# -- the cases: for every source device its rows in storage order, each
# (destination, passes the filter, a is NULL) ------------------------------


def _mixed(src):
    # every destination from every source, dead rows in between, the
    # destinations out of order: the sort has work to do
    return [((r * 7 + src) % D, r % 5 != 0, r % 3 == 0) for r in range(27)]


def _nulls_at_edges(src):
    # sorted by destination the rows are 5 a bucket: NULL in the last
    # row of each bucket and the first of the next
    rows = []
    for r in range(20):
        d, k = (r + src) % D, r // D
        rows.append((d, True, k in (0, 4)))
    return rows


def _never_null(src):
    return [((r + src) % D, r % 4 != 1, False) for r in range(22)]


def _full_bucket(src):
    # 16 rows from device 0 to device 1: cap is 16, that bucket is full
    if src == 0:
        return [(1, True, r % 2 == 0) for r in range(16)] + [
            (r % D, True, False) for r in range(9)]
    return [(r % D, True, r % 2 == 1) for r in range(13)]


def _empty_destination(src):
    # nobody sends device 2 anything; device 3 sends only to itself
    if src == 3:
        return [(3, True, False) for _ in range(6)]
    return [((0, 1, 3)[r % 3], r % 7 != 0, r % 2 == 0) for r in range(24)]


def _all_masked(src):
    return [((r + src) % D, False, r % 2 == 0) for r in range(18)]


def _one_destination(src):
    return [(3, r % 6 != 0, r % 4 == 0) for r in range(30)]


def _ends_at_n(src):
    # 32 live rows fill a device's 32 padded rows and the last bucket
    # ends at row n: its slice of cap rows would start past n - cap
    return [((r * 5 + src) % D, True, r % 8 == 0) for r in range(32)]


CASES = {
    "int64_keys_mixed": _mixed,
    "nulls_both_sides_of_a_bucket_edge": _nulls_at_edges,
    "never_null_columns": _never_null,
    "bucket_filled_to_cap": _full_bucket,
    "destination_receives_nothing": _empty_destination,
    "every_row_masked_out": _all_masked,
    "every_row_to_one_destination": _one_destination,
    "last_bucket_ends_at_row_n": _ends_at_n,
}


def _routed(locator, key, values):
    return np.asarray(locator.route_insert(
        {key: Column(ty.INT8, values, None, None)}, len(values)))


@pytest.fixture(scope="module")
def mesh():
    """Four datanodes on four virtual devices, the target table ``u``
    and one source table a case, each distributed by its ``id`` and
    redistributed by ``x`` onto ``u``'s placement. Returns the cluster,
    a session and per case the rows every device holds, in order."""
    import jax

    from opentenbase_tpu.executor.fused import FusedExecutor, build_mesh

    c = Cluster(num_datanodes=D, shard_groups=64)
    s = c.session()
    s.execute("create table u (uk bigint, uv bigint) "
              "distribute by shard(uk)")
    for name in CASES:
        s.execute(f"create table t_{name} (id bigint, x bigint, f int, "
                  "a int, b bigint) distribute by shard(id)")
    # where the locators put an id (source device) and a key
    # (destination): int64 keys on both sides of 32 bits and of zero
    ids = np.arange(4000, dtype=np.int64)
    at = _routed(c.catalog.get("t_" + next(iter(CASES))).locator, "id", ids)
    id_pool = [list(ids[at == n]) for n in range(D)]
    keys = np.concatenate([
        np.arange(1, 1500, dtype=np.int64) * 3_000_000_019,
        -np.arange(1, 1500, dtype=np.int64) * 7_000_000_001,
    ])
    to = _routed(c.catalog.get("u").locator, "uk", keys)
    key_pool = [list(keys[to == n]) for n in range(D)]
    s.execute("insert into u values " + ",".join(
        f"({k},{i})" for i, k in enumerate(keys)))
    held = {}
    for name, rows_of in CASES.items():
        per_dev, values = [], []
        for src in range(D):
            spec = rows_of(src)
            rows = {k: [] for k in ("dest", "live", "x", "f", "a", "av", "b")}
            for r, (dest, live, null) in enumerate(spec):
                i = id_pool[src][r]
                x = key_pool[dest][(r * 11 + src) % len(key_pool[dest])]
                a = -(r + 1) * (src + 1)
                for k, v in (("dest", dest), ("live", live), ("x", x),
                             ("f", int(live)), ("a", 0 if null else a),
                             ("av", not null), ("b", i * 1_000_003 + 5)):
                    rows[k].append(v)
                values.append(
                    f"({i},{x},{int(live)},{'null' if null else a},"
                    f"{i * 1_000_003 + 5})")
            per_dev.append(rows)
        s.execute(f"insert into t_{name} values " + ",".join(values))
        held[name] = per_dev
    s.execute("analyze")
    # estimates alone choose the motion: far beyond the broadcast limit,
    # the smaller side moves onto the larger's placement
    c.catalog.get("u").stats["rows"] = 40_000_000
    for name in CASES:
        c.catalog.get(f"t_{name}").stats["rows"] = 9_000_000
    c._fused = FusedExecutor(
        c.catalog, c.stores, mesh=build_mesh(jax.devices()[:D]))
    return c, s, held


def _query(name):
    return (f"select sum(a), sum(b), count(*) from t_{name}, u "
            "where x = uk and f = 1")


@pytest.fixture(scope="module")
def ran(mesh):
    """``ran(case)``: the case's statement on the mesh, once; what
    ``_run_exchange`` gave and every (program, arguments) launched (a
    second run would find the count pass cached and launch none)."""
    c, s, _held = mesh
    memo = {}

    def run(name):
        if name in memo:
            return memo[name]
        outs, launched = [], []
        real_run = fused_dag.DagRunner._run_exchange
        real_launch = fused_dag.DagRunner._launch

        def run_exchange(self, *a, **kw):
            outs.append(real_run(self, *a, **kw))
            return outs[-1]

        def launch(self, prog, arrays, params, snap, **kw):
            launched.append((prog, (tuple(arrays), params, snap)))
            return real_launch(self, prog, arrays, params, snap, **kw)

        s.execute("set enable_fused_execution = off")
        host = s.query(_query(name))
        s.execute("set enable_fused_execution = on")
        done = c._fused._dag.completed if c._fused._dag else 0
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(fused_dag.DagRunner, "_run_exchange", run_exchange)
            mp.setattr(fused_dag.DagRunner, "_launch", launch)
            assert s.query(_query(name)) == host
        dag = c._fused._dag
        assert dag.completed == done + 1, dag.unsupported
        memo[name] = outs, launched
        return memo[name]

    return run


@pytest.mark.parametrize("name", list(CASES))
def test_received_slabs_equal_stable_bucketing(mesh, ran, name):
    outs, _launched = ran(name)
    (out,) = outs
    assert set(out) == {"cols", "valids", "counts", "cap", "schema"}
    assert [col.name for col in out["schema"]] == list(NAMES)
    cap = out["cap"]
    per_dev = mesh[2][name]
    refs = []
    for rows in per_dev:
        cols = {
            "x": np.array(rows["x"], np.int64),
            "f": np.array(rows["f"], np.int32),
            "a": np.array(rows["a"], np.int32),
            "b": np.array(rows["b"], np.int64),
        }
        valids = {k: np.ones(len(rows["dest"]), bool) for k in cols}
        valids["a"] = np.array(rows["av"], bool)
        refs.append(bucket_reference(
            np.array(rows["dest"]), np.array(rows["live"], bool),
            cols, valids, 1 << 10))
    widest = max(int(r[0].max()) for r in refs)
    assert cap == max(16, 1 << max(widest - 1, 0).bit_length())
    counts = np.asarray(out["counts"]).reshape(D, D)  # [dest, src]
    got_cols = [np.asarray(x).reshape(D, D, cap) for x in out["cols"]]
    got_valids = [np.asarray(x).reshape(D, D, cap) for x in out["valids"]]
    assert [x.dtype for x in got_cols] == [
        np.int64, np.int32, np.int32, np.int64]
    for dest in range(D):
        for src in range(D):
            rcount, rdata, rvalid = refs[src]
            k = int(rcount[dest])
            assert counts[dest, src] == k, (dest, src)
            for ci, col in enumerate(NAMES):
                valid = got_valids[ci][dest, src, :k]
                want = rvalid[col][dest, :k]
                assert valid.dtype == np.bool_
                assert np.array_equal(valid, want), (col, dest, src)
                # a NULL's data cell is nobody's to read
                assert np.array_equal(
                    got_cols[ci][dest, src, :k][want],
                    rdata[col][dest, :k][want]), (col, dest, src)


def _lowered(prog, args) -> str:
    """The program as lowered, before any compiler pass (StableHLO),
    with the table of its ops' names: ``[jit(..)/../]otb/<scope>/<op>``."""
    return prog.lower(*args).as_text(debug_info=True)


def _count(text: str, op: str) -> int:
    return len(re.findall(rf"stablehlo\.{op}\b", text))


def _names(text: str, op: str) -> list:
    """The distinct traced names of the ``op`` primitives."""
    return [n for n in set(re.findall(r'loc\("([^"]*/[^"]*)"\(', text))
            if n.endswith("/" + op)]


@pytest.fixture(scope="module")
def lowered(ran):
    """Lowered text of the count pass and the exchange program of a
    plain scan -> filter -> redistribute fragment."""
    _outs, launched = ran("int64_keys_mixed")
    by_name = {}
    for prog, args in launched:
        by_name.setdefault(prog.__name__, _lowered(prog, args))
    return by_name


def test_exchange_program_sorts_once_and_never_scatters(lowered):
    text = lowered["program_dag_exchange"]
    assert _count(text, "all_to_all") == 6  # 4 columns, 1 word, counts
    assert _count(text, "scatter") == 0
    assert _count(text, "while") == 0
    # the payload rides ONE sort: key, four columns, one validity word
    assert _count(text, "sort") == 1
    (sort,) = _names(text, "sort")
    assert "otb/exchange/bucket/sort/" in sort
    (operands,) = re.findall(r'"stablehlo\.sort"\(([^)]*)\)', text)
    assert len(operands.split(",")) == 6
    # and every bucket is a slice of a sorted column
    assert _count(text, "dynamic_slice") == D * 5
    assert all("otb/exchange/bucket/slab/" in n
               for n in _names(text, "dynamic_slice"))
    assert all("otb/exchange/bucket/all_to_all/" in n
               for n in _names(text, "all_to_all"))


def test_count_pass_never_scatters(lowered):
    text = lowered["program_dag_count"]
    assert _count(text, "reduce") >= D, "not the count pass"
    assert _count(text, "scatter") == 0
    assert _count(text, "while") == 0
    assert _count(text, "sort") == 0
