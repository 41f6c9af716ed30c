"""Distributed query phase 2: grouped partial aggregation + shuffle joins.

Property-based equivalence suite: every distributed result (grouped
aggregation over 1/2/4 shards, both placements, R=2 replication, shuffled
equi-joins, replica death mid-query) must be element-equal to the
single-node ``query.engine`` oracle run over the same rows.  Structure
(row count, group cardinality, key dtype, shard count, placement) is drawn
by hypothesis; bulk values come from a numpy generator seeded by a drawn
seed.

Equality contract: group keys, counts, integer sums and extrema compare
exactly; float sums/means compare within 1e-9 relative (distributed merge
adds partial sums in a different order than the single-pass oracle).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import RecordBatch
from repro.core.flight import (
    FaultInjector,
    FlightClusterClient,
    FlightClusterServer,
)
from repro.query import (
    QueryPlan,
    aggregate,
    col,
    hash_join,
    merge_partials,
    partial_aggregate,
    partial_schema,
)

AGGS = [("sum", "v"), ("mean", "v"), ("min", "i"), ("max", "i"), ("count", "v")]


def build_table(kind: str, n: int, card: int, masked: bool, seed: int):
    """One logical table: group key column ``g`` (dtype ``kind``, ``card``
    distinct values), float values ``v``, int values ``i`` — plus a ragged
    batch split (including zero-row batches) of the same rows."""
    rng = np.random.default_rng(seed)
    gidx = rng.integers(0, card, n)
    if kind == "int64":
        g = (gidx.astype(np.int64) * 3) - card
    elif kind == "float64":
        pool = np.arange(card) * 0.75 - 1.0
        pool[0] = -0.0  # -0.0 / 0.0 must canonicalize to one group
        g = pool[gidx]
    else:  # utf8, optionally with a null group (masked varlen keys)
        pool = [f"key-{j}" for j in range(card)]
        if masked:
            pool[0] = None
        g = [pool[j] for j in gidx]
    data = {
        "g": g,
        "v": rng.normal(scale=100.0, size=n),
        "i": rng.integers(-(10**6), 10**6, n).astype(np.int64),
    }
    whole = RecordBatch.from_pydict(data)
    cuts = sorted(int(c) for c in rng.integers(0, n + 1, 3))
    bounds = [0, *cuts, n]
    batches = [whole.slice(a, b - a) for a, b in zip(bounds, bounds[1:])]
    return whole, batches


def scalar_eq(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float) and a != a and b != b:
        return True  # NaN key/result == NaN key/result
    return a == b


def assert_grouped_equal(oracle: RecordBatch, got: RecordBatch) -> None:
    od, gd = oracle.to_pydict(), got.to_pydict()
    assert list(od) == list(gd)
    assert got.num_rows == oracle.num_rows
    for name in od:
        if name.startswith(("sum(", "mean(")):
            np.testing.assert_allclose(gd[name], od[name], rtol=1e-9, atol=1e-12)
        else:  # keys, counts, integer extrema: exact
            assert all(scalar_eq(o, g) for o, g in zip(od[name], gd[name])), name


def assert_scalars_equal(oracle: dict, got: dict) -> None:
    assert set(oracle) == set(got)
    for k in oracle:
        if k.startswith(("sum(", "mean(")):
            np.testing.assert_allclose(got[k], oracle[k], rtol=1e-9, atol=1e-12)
        else:
            assert scalar_eq(oracle[k], got[k]), k


def make_cluster(shards: int, scheme: str, replicas: int = 1) -> FlightClusterServer:
    kw = {"hash_key": "g"} if scheme == "hash" else {}
    return FlightClusterServer(num_shards=shards, placement=scheme,
                               replicas=replicas, **kw)


# --------------------------------------------------------------------------
# property: distributed grouped aggregation == single-node oracle
# --------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_prop_grouped_aggregation_distributed_equals_oracle(data):
    n = data.draw(st.integers(1, 120))
    card = data.draw(st.integers(1, n))  # 1 group .. one group per row
    kind = data.draw(st.sampled_from(["int64", "float64", "utf8"]))
    masked = data.draw(st.booleans())
    shards = data.draw(st.sampled_from([1, 2, 4]))
    scheme = data.draw(st.sampled_from(["round_robin", "hash"]))
    filtered = data.draw(st.booleans())
    seed = data.draw(st.integers(0, 2**31 - 1))
    whole, batches = build_table(kind, n, card, masked, seed)
    plan = QueryPlan("t", aggregations=AGGS, group_by=["g"],
                     predicate=(col("v") > 0.0) if filtered else None)
    cl = make_cluster(shards, scheme)
    try:
        cl.add_dataset("t", batches)
        got, _ = FlightClusterClient(cl).aggregate(plan)
        assert_grouped_equal(aggregate(plan, [whole]), got)
    finally:
        cl.shutdown()


@settings(max_examples=8, deadline=None)
@given(st.data())
def test_prop_grouped_aggregation_replicated_equals_oracle(data):
    n = data.draw(st.integers(1, 100))
    card = data.draw(st.integers(1, n))
    kind = data.draw(st.sampled_from(["int64", "utf8"]))
    seed = data.draw(st.integers(0, 2**31 - 1))
    whole, batches = build_table(kind, n, card, masked=False, seed=seed)
    plan = QueryPlan("t", aggregations=AGGS, group_by=["g"])
    cl = make_cluster(shards=3, scheme="round_robin", replicas=2)
    try:
        cl.add_dataset("t", batches)
        got, _ = FlightClusterClient(cl).aggregate(plan)
        assert_grouped_equal(aggregate(plan, [whole]), got)
    finally:
        cl.shutdown()


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_prop_ungrouped_scalars_distributed_equals_oracle(data):
    n = data.draw(st.integers(1, 120))
    shards = data.draw(st.sampled_from([1, 2, 4]))
    scheme = data.draw(st.sampled_from(["round_robin", "hash"]))
    threshold = data.draw(st.floats(-150.0, 150.0))
    seed = data.draw(st.integers(0, 2**31 - 1))
    whole, batches = build_table("int64", n, max(1, n // 3), False, seed)
    # the threshold can empty every shard — the (sum, count) state must
    # still merge to count 0 / NaN mean, never poison other shards
    plan = QueryPlan("t", aggregations=AGGS, predicate=col("v") > threshold)
    cl = make_cluster(shards, scheme)
    try:
        cl.add_dataset("t", batches)
        got, _ = FlightClusterClient(cl).aggregate(plan)
        assert isinstance(got, dict)
        assert_scalars_equal(aggregate(plan, [whole]), got)
    finally:
        cl.shutdown()


# --------------------------------------------------------------------------
# property: shuffled equi-join == single-node hash_join oracle
# --------------------------------------------------------------------------


def _row_set(batches, names):
    return sorted(
        tuple(row) for b in batches
        for row in zip(*[b.to_pydict()[c] for c in names])
    )


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_prop_shuffle_join_distributed_equals_oracle(data):
    n_l = data.draw(st.integers(1, 80))
    n_r = data.draw(st.integers(1, 80))
    card = data.draw(st.integers(1, 25))
    kind = data.draw(st.sampled_from(["int64", "utf8"]))
    shards = data.draw(st.sampled_from([2, 4]))
    replicas = data.draw(st.sampled_from([1, 2]))
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)

    def side(m, vname):
        gidx = rng.integers(0, card, m)
        if kind == "int64":
            k = gidx.astype(np.int64) * 2
        else:
            pool = [f"j{j}" for j in range(card)]
            k = [pool[j] for j in gidx]
        d = {"k": k, vname: rng.normal(size=m)}
        whole = RecordBatch.from_pydict(d)
        cut = int(rng.integers(0, m + 1))
        return whole, [whole.slice(0, cut), whole.slice(cut)]

    lw, lb = side(n_l, "x")
    rw, rb = side(n_r, "y")
    oracle = hash_join([lw], [rw], ["k"])
    cl = FlightClusterServer(num_shards=shards, replicas=replicas)
    try:
        cl.add_dataset("L", lb)
        cl.add_dataset("R", rb)
        cc = FlightClusterClient(cl)
        table, _ = cc.join("L", "R", "k", "J")
        assert [f.name for f in oracle.schema.fields] == ["k", "x", "y"]
        assert _row_set(table.batches, ["k", "x", "y"]) == \
               _row_set([oracle], ["k", "x", "y"])
    finally:
        cl.shutdown()


# --------------------------------------------------------------------------
# partial/final mean regression (the concat-then-average bug)
# --------------------------------------------------------------------------


class TestPartialFinalContract:
    def test_mean_state_is_sum_count_pair(self):
        whole, _ = build_table("int64", 50, 5, False, seed=3)
        plan = QueryPlan("t", aggregations=[("mean", "v")], group_by=["g"])
        ps = partial_schema(plan, whole.schema)
        assert ps.names == ["g", "mean(v)#sum", "mean(v)#cnt"]
        state = partial_aggregate(plan, [whole])
        s = state.column("mean(v)#sum").to_numpy()
        c = state.column("mean(v)#cnt").to_numpy()
        assert c.sum() == 50
        merged = merge_partials(plan, [state])
        np.testing.assert_allclose(
            merged.column("mean(v)").to_numpy(), s / c, rtol=0, atol=0)

    def test_merge_of_partials_matches_oracle_on_pathological_splits(self):
        """Empty batches, empty-after-filter shards, ragged splits: the
        merged (sum, count) state stays within 1e-9 of the one-pass oracle
        (the retired concat-then-average path returned NaN for any shard
        whose filter emptied a group)."""
        whole, _ = build_table("int64", 300, 7, False, seed=11)
        plan = QueryPlan("t", aggregations=[("mean", "v"), ("sum", "v"),
                                            ("count", "v")],
                         group_by=["g"], predicate=col("v") > 25.0)
        # pathological split: leading/trailing empties, a 1-row sliver, rest
        splits = [whole.slice(0, 0), whole.slice(0, 1), whole.slice(1, 149),
                  whole.slice(150, 0), whole.slice(150, 150)]
        partials = [partial_aggregate(plan, [s], whole.schema) for s in splits]
        merged = merge_partials(plan, partials)
        assert_grouped_equal(aggregate(plan, [whole]), merged)

    def test_empty_after_filter_scalar_mean_is_nan_count_zero(self):
        whole, _ = build_table("int64", 40, 4, False, seed=5)
        plan = QueryPlan("t", aggregations=[("mean", "v"), ("count", "v")],
                         predicate=col("v") > 1e9)
        out = aggregate(plan, [whole])
        assert out["count(v)"] == 0.0
        assert out["mean(v)"] != out["mean(v)"]  # NaN, not a crash or 0

    def test_partial_of_empty_shard_merges_cleanly(self):
        whole, _ = build_table("int64", 60, 6, False, seed=9)
        plan = QueryPlan("t", aggregations=AGGS, group_by=["g"])
        full = partial_aggregate(plan, [whole])
        empty = partial_aggregate(plan, [], schema=whole.schema)
        assert empty.num_rows == 0
        merged = merge_partials(plan, [empty, full, empty])
        assert_grouped_equal(aggregate(plan, [whole]), merged)


# --------------------------------------------------------------------------
# fault-interleaved: replica death mid-grouped-query
# --------------------------------------------------------------------------


class TestFaultInterleavedQuery:
    def test_kill_replica_mid_grouped_query_is_oracle_equal(self):
        """R=2 over TCP: kill one replica after the query is planned but
        before its partial streams drain.  The scheduler fails the dead
        primary's endpoints over to the surviving holders — the merged
        result equals the oracle with zero client-visible errors."""
        whole, batches = build_table("int64", 3000, 17, False, seed=21)
        cl = FlightClusterServer(num_shards=3, replicas=2).serve_tcp()
        try:
            cl.add_dataset("big", batches)
            cc = FlightClusterClient(
                f"tcp://127.0.0.1:{cl.port}", max_streams=3, window=2)
            plan = QueryPlan("big", aggregations=AGGS, group_by=["g"])
            info = cc.query_info(plan)
            FaultInjector(cl).kill(0)  # verbs fail + connections sever
            table, _ = cc.scheduler().fetch(info)
            assert table.batches, "no partial states drained"
            got = merge_partials(plan, list(table.batches))
            assert_grouped_equal(aggregate(plan, [whole]), got)
        finally:
            cl.shutdown()

    @pytest.mark.slow
    def test_grouped_queries_survive_replica_churn(self):
        """Churn variant: repeated grouped queries while replicas die and
        revive between (and across) rounds — every merged result stays
        oracle-equal and no round surfaces an error."""
        whole, batches = build_table("int64", 2000, 11, False, seed=33)
        cl = FlightClusterServer(num_shards=4, replicas=2).serve_tcp()
        try:
            cl.add_dataset("big", batches)
            cc = FlightClusterClient(
                f"tcp://127.0.0.1:{cl.port}", max_streams=4, window=2)
            plan = QueryPlan("big", aggregations=AGGS, group_by=["g"])
            oracle = aggregate(plan, [whole])
            inj = FaultInjector(cl)
            for round_ in range(6):
                victim = round_ % 4
                inj.kill(victim)
                # fresh scheduler per round: connections severed by the
                # kill must not be replayed from the client cache
                got, _ = cc.aggregate(plan, max_streams=4)
                assert_grouped_equal(oracle, got)
                inj.revive(victim)
        finally:
            cl.shutdown()
