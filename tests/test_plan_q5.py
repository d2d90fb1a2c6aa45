"""TPC-DS q5 in the query template's full shape (examples/nds.py) through
every tier, and what the SPMD walk owes a deployment whose tables live on
their chips: a mesh given as a device count, born-sharded inputs adopted
in place, no operator between the scans and the sink off the mesh, and
the exchange each join was planned with. Four of the eight virtual
devices tests/conftest.py forces."""
import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.parallel import make_mesh
from spark_rapids_tpu.plan import PlanExecutor
from spark_rapids_tpu.plan import distributed as dist
from spark_rapids_tpu.plan.nodes import Exchange, HashJoin

from examples import nds

N = 6_000
ORDERED = ["channel", "id"]
# seed -> what the generated tables hold beside the usual (most returns'
# sales lie outside the window; half the stores and sites share their
# business id with another surrogate key)
CASES = {3: {}, 5: {"empty": ("web",)}, 11: {"empty": ("store",),
                                             "n_pages": 40}}
TIERS = {"eager": dict(mode="eager"),
         "capped": dict(mode="capped", caps={"key_cap": 2048}),
         "mesh": dict(mode="eager", mesh=4)}


def bound(seed):
    return (nds.q5_plan(),
            nds.q5_inputs(*nds.q5_tables(N, seed, **CASES[seed])))


def assert_matches(res, ref):
    got = res.compact() if res.mode == "capped" else res.table
    assert list(got.names) == list(ref.columns)
    nds.assert_rows_equal(pd.DataFrame(got.to_pydict()), ref, ORDERED,
                          res.mode)


@pytest.mark.parametrize("tier", list(TIERS))
@pytest.mark.parametrize("seed", list(CASES))
def test_q5_template_matches_pandas(seed, tier):
    ref = nds.q5_reference(N, seed, **CASES[seed])
    # the cases hold what they are named for
    assert (ref.channel == -1).sum() == 1 and ref.id.iloc[0] == -1
    empty = [nds.Q5_CHANNELS.index(c) for c in CASES[seed].get("empty", ())]
    assert not ref.channel.isin(empty).any()
    res = PlanExecutor(**TIERS[tier]).execute(*bound(seed))
    assert_matches(res, ref)
    if tier == "mesh":
        assert res.local_ops == 0 and res.dist_ops > 0


def test_q5_data_holds_the_templates_hard_cases():
    t = {k: pd.DataFrame(v) for k, v in nds.q5_datagen(N, 3).items()}
    lo, hi = nds.Q5_DATE_LO + nds.Q5_JULIAN, nds.Q5_DATE_HI + nds.Q5_JULIAN
    wr = t["web_returns"].merge(
        t["web_sales"], left_on=["wr_item_sk", "wr_order_number"],
        right_on=["ws_item_sk", "ws_order_number"])
    assert len(wr) == len(t["web_returns"])         # every return, one sale
    returned_in = wr.wr_returned_date_sk.between(lo, hi)
    sold_in = wr.ws_sold_date_sk.between(lo, hi)
    assert (returned_in & ~sold_in).any()           # sale outside the window
    assert t["store"].s_store_id.duplicated().any()     # two keys, one id
    assert t["web_site"].web_site_id.duplicated().any()


# ---- the mesh as a number -------------------------------------------------------

def test_mesh_width_equals_the_mesh_it_builds():
    by_count, by_mesh = PlanExecutor(mesh=4), PlanExecutor(mesh=make_mesh(4))
    assert by_count.mesh == by_mesh.mesh
    assert by_count.mesh.shape == {"data": 4}
    assert list(by_count.mesh.devices.flat) == jax.devices()[:4]
    a = by_count.execute(*bound(3))
    b = by_mesh.execute(*bound(3))
    assert a.table.to_pydict() == b.table.to_pydict()


def test_mesh_wider_than_the_visible_devices_raises():
    with pytest.raises(ValueError, match="devices are visible"):
        PlanExecutor(mesh=len(jax.devices()) + 1)


# ---- the walk stays on the mesh ------------------------------------------------

@pytest.fixture
def planned(monkeypatch):
    """q5 over four devices with a broadcast threshold below the test's
    `catalog_page` (300 rows) and `web_returns` (200), above `store` (12),
    `web_site` (6) and the date window (15)."""
    monkeypatch.setenv("SPARK_RAPIDS_TPU_BROADCAST_ROWS", "64")
    res = PlanExecutor(mesh=4).execute(*bound(3))
    assert_matches(res, nds.q5_reference(N, 3))
    return res


def test_no_operator_between_scans_and_sink_is_local(planned):
    res = planned
    root = res.plan.root
    assert isinstance(root, Exchange) and root.how == "gather"
    for node in res.plan.nodes:
        m = res.metrics[node.label]
        if node is root:
            assert m.exchange_how == "gather"
            continue
        assert m.sharding and m.sharding != "local", (node.label, m.sharding)
        assert m.n_peers == 4
    assert res.local_ops == 0
    assert res.dist_ops == len(res.plan.nodes) - 1
    edges = [m for m in res.metrics.values() if m.exchange_how]
    assert res.exchange_edges >= len(edges)
    assert res.exchange_bytes == sum(m.exchange_bytes for m in edges)


def test_each_join_moves_data_as_it_was_planned(planned):
    res = planned
    how = {}
    for node in res.plan.nodes:
        if isinstance(node, HashJoin):
            sides = [c.how if isinstance(c, Exchange) else None
                     for c in node.children]
            # the build_side rule may have swapped an inner join's sides
            how[tuple(node.right_keys)] = sides
            how[tuple(node.left_keys)] = sides[::-1]
            for c in node.children:
                if isinstance(c, Exchange):
                    assert res.metrics[c.label].exchange_how == c.how
    assert how[("ws_item_sk", "ws_order_number")] == ["hash", "hash"]
    assert how[("cp_catalog_page_sk",)] == ["hash", "hash"]
    assert how[("s_store_sk",)] == [None, "broadcast"]
    assert how[("web_site_sk",)] == [None, "broadcast"]
    assert how[("d_date_sk",)] == [None, "broadcast"]
    moved = {m.exchange_how for m in res.metrics.values() if m.exchange_how}
    assert moved >= {"hash", "broadcast", "reduce", "range", "gather"}


def test_a_selective_join_packs_its_frame():
    """Above the date window (15 days of five years) every operator runs
    at the size of the rows that are left, not of the scan."""
    n = 120_000
    inputs = nds.q5_inputs(*nds.q5_tables(n, 7))
    res = PlanExecutor(mesh=4).execute(nds.q5_plan(), inputs)
    ref = nds.q5_reference(n, 7)
    assert_matches(res, ref)
    frames = {}
    for node in res.plan.nodes:
        if isinstance(node, HashJoin) and \
                tuple(node.right_keys) == ("d_date_sk",):
            m = res.metrics[node.label]
            frames[node.label] = (m.rows_in, m.rows_out, m.bytes_out)
    assert len(frames) == 3
    for rows_in, rows_out, bytes_out in frames.values():
        assert rows_out * 5 < rows_in            # the window is narrow
        # seven int64 columns and their frame: within twice the live rows
        assert bytes_out <= 2 * rows_out * 7 * 8 + 7 * 8 * 64


def test_a_broadcast_window_arrives_at_its_live_rows(monkeypatch):
    """The 15 days of the calendar lie on one shard of four: the filter
    leaves 15 slots on every shard, 60 in all, and the broadcast relation
    must hold 15, or each probe row meets 45 dead build slots (on the chip
    that pushed two date joins past the lookup join's work bound and into
    sort joins of 39.6 M rows). Every date join is a lookup join."""
    built = []
    real = dist.DistContext._lookup_join

    def lookup(self, node, l, r, lk, rk, specs):
        out = real(self, node, l, r, lk, rk, specs)
        built.append((tuple(rk), r.padded_rows, out is not None))
        return out

    monkeypatch.setattr(dist.DistContext, "_lookup_join", lookup)
    inputs = nds.q5_inputs(*nds.q5_tables(N, 3))
    res = PlanExecutor(mesh=4).execute(nds.q5_plan(), inputs)
    assert_matches(res, nds.q5_reference(N, 3))
    assert [b for b in built if b[0] == ("d_date_sk",)] == \
        [(("d_date_sk",), 15, True)] * 3


@pytest.mark.parametrize("what", ["hash exchange", "compaction"])
def test_a_capacity_below_the_rows_it_was_counted_for_raises(monkeypatch,
                                                              what):
    """The exchange ships buckets of the size a first program counted, and
    a compaction packs into the slots a count gave: if the two programs
    ever disagree, rows are lost. Both say so, and the walk raises."""
    from spark_rapids_tpu.parallel.autoretry import CapacityOverflowError
    real = dist._raise_if_lost
    seen = []

    def half(n):
        return max(int(n) // 2, 1)

    def watch(lost, name, cap):
        seen.append(name)
        if name == what:
            real(lost, name, cap)

    monkeypatch.setattr(dist, "bucket", half)
    monkeypatch.setattr(dist, "_raise_if_lost", watch)
    # below `web_returns` (200 rows): the returns-to-sales join exchanges
    monkeypatch.setenv("SPARK_RAPIDS_TPU_BROADCAST_ROWS", "64")
    dist._JIT_PRIMS.clear()
    with pytest.raises(CapacityOverflowError, match=what):
        PlanExecutor(mesh=4, degrade="off").execute(*bound(3))
    dist._JIT_PRIMS.clear()
    assert what in seen


# ---- born-sharded inputs ---------------------------------------------------------

def _pointers(a):
    return sorted(s.data.unsafe_buffer_pointer() for s in a.addressable_shards)


def test_born_sharded_input_is_adopted_in_place():
    mesh = make_mesh(4)
    spec = NamedSharding(mesh, P("data"))
    host = np.arange(4096, dtype=np.int64)
    born = jax.device_put(jnp.asarray(host), spec)
    t = Table([Column(dtype=dtypes.INT64, length=4096, data=born)],
              names=["k"])
    rel = dist.shard_table(mesh, "data", t)
    assert rel.table["k"].data is born
    assert _pointers(rel.table["k"].data) == _pointers(born)
    assert rel.padded_rows == 4096 and rel.num_rows == 4096
    assert rel.valid.sharding.is_equivalent_to(spec, 1)
    # through a whole plan: the scan's output IS the input
    from spark_rapids_tpu.plan import PlanBuilder
    plan = PlanBuilder().scan("t", schema=["k"]).build()
    res = PlanExecutor(mesh=mesh).execute(plan, {"t": t})
    assert res.table.to_pydict()["k"] == host.tolist()


def test_rows_no_multiple_of_the_mesh_still_pad():
    mesh = make_mesh(4)
    spec = NamedSharding(mesh, P("data"))
    host = np.arange(4098, dtype=np.int64)
    t = Table([Column(dtype=dtypes.INT64, length=4098,
                      data=jnp.asarray(host))], names=["k"])
    rel = dist.shard_table(mesh, "data", t)
    assert rel.padded_rows == 4100 and rel.num_rows == 4098
    assert rel.table["k"].data.sharding.is_equivalent_to(spec, 1)
    assert np.asarray(rel.valid).sum() == 4098
    assert np.asarray(rel.table["k"].data)[:4098].tolist() == host.tolist()


def test_bucket_is_within_an_eighth_above_its_count():
    for n in (0, 1, 8, 9, 15, 16, 17, 1000, 411_234, 39_600_000):
        cap = dist.bucket(n)
        assert cap >= max(n, 8) and cap <= max(n, 8) * 9 // 8 + 1
        assert dist.bucket(cap) == cap


def test_a_fan_out_from_the_shorter_side_needs_no_escalation(monkeypatch):
    """Two partitioned sides, the shorter one a dimension that every fact
    row matches (40 rows a key): the join's frame is sized from the spans
    it has counted, so the shorter side may probe and nothing overflows
    (on the chip a capacity guessed from the probe side's slots escalated
    six times, a compile each, and still failed: PERF.md, PR 32)."""
    from spark_rapids_tpu.plan import PlanBuilder
    monkeypatch.setenv("SPARK_RAPIDS_TPU_BROADCAST_ROWS", "64")
    rng = np.random.default_rng(5)
    n_fact, n_dim = 200_000, 5_000

    def table(cols):
        return Table([Column(dtype=dtypes.INT64, length=len(a),
                             data=jnp.asarray(a)) for a in cols.values()],
                     names=list(cols))
    fact = {"k": rng.integers(0, n_dim, n_fact).astype(np.int64),
            "v": rng.integers(1, 100, n_fact).astype(np.int64)}
    dim = {"dk": np.arange(n_dim, dtype=np.int64),
           "g": (np.arange(n_dim, dtype=np.int64) % 7)}
    b = PlanBuilder()
    plan = (b.scan("fact", schema=["k", "v"])
            .join(b.scan("dim", schema=["dk", "g"]), left_on="k",
                  right_on="dk")
            .aggregate(["g"], [("v", "sum", "total")]).sort(["g"]).build())
    res = PlanExecutor(mesh=4).execute(plan, {"fact": table(fact),
                                              "dim": table(dim)})
    join = next(m for m in res.metrics.values() if m.kind == "HashJoin")
    assert join.exchange_how == "" and join.rows_out == n_fact
    sides = [c for c in next(n for n in res.plan.nodes
                             if isinstance(n, HashJoin)).children]
    assert [c.how for c in sides] == ["hash", "hash"]
    want = pd.DataFrame(fact).assign(g=lambda d: d.k % 7) \
        .groupby("g").v.sum()
    assert res.table.to_pydict() == {"g": want.index.tolist(),
                                     "total": want.values.tolist()}
    assert res.dist_cap_escalations == 0 and res.local_ops == 0
