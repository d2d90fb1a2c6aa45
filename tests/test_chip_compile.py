"""Compile for the chip, without the chip.

Every Pallas call that is registered for `tpu` (fused_select, hash_join,
topk), plus `hash_pallas`, the partition histogram, the input digest's
fold and the capped q3 program, lowered and compiled for a DESCRIBED
v5e:2x2 device by the TPU compiler installed here
(`/opt/skills/guides/on-chip-measurement`, §2).
Interpret-mode parity (tests/test_kernel_registry.py) cannot see what
Mosaic refuses — block shapes, 64-bit values, boolean loop carries — and
`interpret = jax.default_backend() != "tpu"` keeps every other test off
that path. A compile that passes here is not a chip run: a cell of
`chipbench` is.

The topology is described inside a module-scoped fixture (never at import:
only one process may hold libtpu, and every xdist worker imports this
file), the compiles run in the test's own process, and the persistent
compile cache is off around them (an executable built for a described
device cannot be read back without one).
"""
import base64
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

import spark_rapids_tpu  # noqa: F401  (x64 on — the regime Mosaic sees)
from spark_rapids_tpu import Column, Table, dtypes

# a 10M-row fact table against examples/nds.py's q3 date dimension
N_FACT = 10_000_000
N_DATES = 3_650
N_DATES_KEPT = 310          # d_moy == 11: the hash join's build side


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(one_chip, no_persistent_cache, monkeypatch):
    """-> compile_calls(fn): trace `fn` (shapes only), capture every
    `pl.pallas_call` it makes, and compile each for the described chip with
    interpret=False. Returns the number of calls compiled."""
    real = pl.pallas_call

    def compile_calls(fn) -> int:
        captured = []

        def capturing(kernel, *a, **kw):
            call = real(kernel, *a, **kw)

            def wrapped(*operands):
                captured.append((kernel, a, kw, [
                    jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
                    for x in operands]))
                return call(*operands)
            return wrapped

        monkeypatch.setattr(pl, "pallas_call", capturing)
        try:
            jax.eval_shape(fn)
        except jax.errors.ConcretizationTypeError:
            # eager entry points sync a count to the host after their
            # kernels ran; the calls before it are already captured
            pass
        finally:
            monkeypatch.setattr(pl, "pallas_call", real)
        for kernel, a, kw, shapes in captured:
            call = real(kernel, *a, **{**kw, "interpret": False})
            jax.jit(call).lower(*shapes).compile()   # raises what Mosaic would
        return len(captured)

    return compile_calls


def _i64(n: int) -> Column:
    return Column(dtype=dtypes.INT64, length=n,
                  data=jnp.zeros((n,), jnp.int64))


def _i32(n: int) -> Column:
    return Column(dtype=dtypes.INT32, length=n,
                  data=jnp.zeros((n,), jnp.int32))


def test_registered_tpu_kernels_are_the_ones_compiled_here():
    """The guard this file exists for: a kernel that lists "tpu" in its
    `backends=` is auto-selected on the chip, so it must have a compile
    test below. Adding a TPU registration without one fails here."""
    from spark_rapids_tpu.ops import (join_pallas, select_pallas,  # noqa
                                      topk_pallas)
    from spark_rapids_tpu.ops.registry import REGISTRY
    on_tpu = {(op, k.name) for op in REGISTRY.ops()
              for k in REGISTRY.kernels(op)
              if "tpu" in k.backends and not k.fallback}
    assert on_tpu == {("fused_select", "pallas"), ("hash_join", "pallas"),
                      ("topk", "pallas"), ("groupby", "direct")}


def test_direct_groupby_compiles_for_v5e(one_chip, no_persistent_cache):
    """The sort-free group-by (plain XLA, registered for the chip): two
    int64 keys under an alive flag, a decimal's four plane sums, a count
    and a min, at a quarter of `q1.tasks`' rows and its key cap of 8."""
    from spark_rapids_tpu.ops.aggregate import _groupby_kernel_direct
    n = 1_500_000

    def shape(dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    kinds = ("sum",) * 4 + ("count", "min", "size")
    compiled = _groupby_kernel_direct.lower(
        (shape(jnp.int32), shape(jnp.int64), shape(jnp.int64)),
        tuple(shape(jnp.int64) for _ in range(6)) + (shape(jnp.int8),),
        tuple(shape(jnp.bool_) for _ in kinds),
        n_ops=3, agg_kinds=kinds, has_valids=(True,) * len(kinds),
        has_alive=True, cap=8).compile()
    assert "sort(" not in compiled.as_text()


def test_fused_select_compiles_for_v5e(chip):
    # the date dimension, with the int32 predicate column the kernel's
    # supports() gate admits (an int64 predicate declines to XLA)
    from spark_rapids_tpu.ops import select_pallas
    from spark_rapids_tpu.plan import col
    t = Table([_i64(N_DATES), _i64(N_DATES), _i32(N_DATES)],
              names=["d_date_sk", "d_year", "d_moy"])
    n = chip(lambda: select_pallas.fused_select_compact(
        t, col("d_moy") == 11, ["d_date_sk", "d_year"], interpret=True))
    assert n == 1


@pytest.mark.parametrize("n_build", [N_DATES_KEPT, 512],
                         ids=["q3-dates", "max-build"])
def test_hash_join_compiles_for_v5e(chip, n_build):
    # q3's eager fact x filtered-dates join: build, count-probe and
    # emit-probe kernels (the capped entry point traces all three)
    from spark_rapids_tpu.ops import join_pallas
    n = chip(lambda: join_pallas.inner_join_capped_pallas(
        [_i64(N_FACT)], [_i64(n_build)], N_FACT // 8, interpret=True))
    assert n == 3


def test_topk_compiles_for_v5e(chip):
    from spark_rapids_tpu.ops import topk_pallas
    t = Table([_i64(N_FACT), _i64(N_FACT)], names=["k", "v"])
    n = chip(lambda: topk_pallas.topk_table(t, ["k"], [False], 100,
                                            interpret=True))
    assert n == 1


def test_partition_histogram_compiles_for_v5e(chip):
    from spark_rapids_tpu.parallel.partition_pallas import histogram_pallas
    n = chip(lambda: histogram_pallas(jnp.zeros((N_FACT,), jnp.int32), 4,
                                      interpret=True))
    assert n == 1


def test_row_hash_compiles_for_v5e(chip):
    # 10M rows x 2 int64, murmur3_32 + xxhash64 fused
    from spark_rapids_tpu.ops import hash_pallas
    t = Table([_i64(N_FACT), _i64(N_FACT)], names=["a", "b"])
    n = chip(lambda: hash_pallas.fused_row_hash(t, interpret=True))
    assert n == 1


# the task cells' fresh tables (chipbench/configs): q3's store_sales batch,
# q72's catalog_sales and inventory
DIGEST_ROWS = [1_440_000, 720_000, 1_996_650]


@pytest.mark.parametrize("n", DIGEST_ROWS)
def test_digest_fold_allocates_nothing_of_a_buffers_size(one_chip,
                                                         no_persistent_cache,
                                                         n):
    """The input digest's fold (serving/cache.py) at the cells' shapes: an
    int64 column and its validity reduce inside one fusion each. A
    materialized widened copy of a column would be 11.5 MB of the
    0.86 GB whose 1% bounds `peak_hbm_gb`."""
    from spark_rapids_tpu.serving import cache

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = cache._fold_buffers.lower(
        shape((4,), jnp.uint32), shape((n,), jnp.int64),
        shape((n,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.output_size_in_bytes < 1 << 12        # 2 x 16 bytes, tiled


def _op_names(text, opcode):
    """The `op_name` of every `opcode` instruction of an executable's text."""
    return [line.split("op_name=\"")[1].split("\"")[0]
            for line in text.splitlines()
            if f" {opcode}(" in line and "op_name=\"" in line]


@pytest.fixture(scope="module")
def capped_q3_compiled(one_chip, no_persistent_cache):
    """The capped tier's one whole-plan program for q3, traced as the chip
    would trace it (registry and kernels see backend "tpu") and compiled
    for the described device. At 8,192 fact rows: XLA's TPU compiler
    spends minutes on this program's nine sorts at any size, and this is
    already the slowest compile of the file — the cell `q3.tasks` compiles
    it at 1.44M rows on the chip."""
    from examples.nds import q3_inputs, q3_plan
    from spark_rapids_tpu.plan import PlanExecutor

    n = 8192
    inputs = q3_inputs(
        Table([_i64(n)] * 3, names=["sold_date_sk", "item_sk",
                                    "price_cents"]),
        Table([_i64(N_DATES)] * 3, names=["d_date_sk", "d_year", "d_moy"]),
        Table([_i64(20_000)] * 3, names=["i_item_sk", "i_brand",
                                         "i_manufact"]))
    captured = {}
    real = PlanExecutor._jitted_capped

    class Captured(Exception):
        pass

    def capturing(self, plan, schemas, caps, input_key):
        fn, bm, km, hit = real(self, plan, schemas, caps, input_key)

        def stop(tables):
            captured["fn"], captured["tables"] = fn, tables
            raise Captured()
        return stop, bm, km, hit

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(PlanExecutor, "_jitted_capped", capturing)
        ex = PlanExecutor(mode="capped", degrade="off",
                          caps=dict(row_cap=max(n // 8, 1024), key_cap=4096))
        with pytest.raises(Captured):
            ex.execute(q3_plan(), inputs)
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            captured["tables"])
        # steer the trace from here, not through an option of the program:
        # code that asks jax.default_backend() must take its TPU branch
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return captured["fn"].lower(shapes).compile()


def test_capped_q3_program_compiles_for_v5e(capped_q3_compiled):
    mem = capped_q3_compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 16 * 1024 ** 3


# the same program of the commit before the joins gathered their live
# prefix (b742d4c, compiled here the same way): every column of a join's
# output was its own flat gather, and the compiler dropped the unread ones
Q3_GATHERS_FLAT = 34
Q3_CODE_BYTES_FLAT = 21_528_064
# and of the commit before the expansion ran in loops (5541a3e): each
# join's general branch held jnp.repeat's scatter-add and four flat gathers
Q3_CODE_BYTES_REPEAT = 21_646_848
Q3_TASKS_PEAK_HBM = 0.497e9         # the cell's `peak_hbm_gb` (ledger, PR 30)


def test_capped_q3_joins_gather_in_loops_and_unread_columns_stay_pruned(
        capped_q3_compiled):
    """Each join gathers its output columns in one loop per side, the
    many-to-one tail its right row ids in another (ops/gather.py:
    gather_live), and the expansion scatters and gathers in three more
    (ops/join.py:expand_rows): twelve `while`s under the two joins'
    scopes. A loop carries every column of its side, and the compiler
    still drops the ones no later operator reads (no more gathers than
    the flat form had). Code lies in HBM beside the data: the loops may
    not cost 1% of the cell's peak, and the expansion in loops is no
    larger than `jnp.repeat` and its flat gathers were."""
    text = capped_q3_compiled.as_text()
    assert text.count(" gather(") <= Q3_GATHERS_FLAT
    loops = _op_names(text, "while")
    assert len(loops) == 12
    assert all(".HashJoin/" in name for name in loops), loops
    assert sum("jit(_expand)/while" in name for name in loops) == 6
    code = capped_q3_compiled.memory_analysis().generated_code_size_in_bytes
    assert code - Q3_CODE_BYTES_FLAT < 0.01 * Q3_TASKS_PEAK_HBM
    assert code <= Q3_CODE_BYTES_REPEAT


def _gather_slots(text):
    """Output slots of every `gather` instruction of an executable's text."""
    return [int(re.search(r"\[(\d+)", line.split(" gather(")[0]).group(1))
            for line in text.splitlines() if " gather(" in line]


def test_capped_join_compiles_with_both_tails_for_v5e(one_chip,
                                                      no_persistent_cache):
    """The capped inner join (ops/join.py:_capped_inner_kernel) at a small
    shape: one conditional whose branches are both in the executable, the
    expansion (its two sorts; its scatter and its gathers in loops over
    the rows that emit and the slots that are live) and the many-to-one
    tail (its one sort, its one gather in a loop over the live prefix);
    the union sort is shared, outside. No gather runs at the cap."""
    from spark_rapids_tpu.ops import join
    from spark_rapids_tpu.ops.gather import live_chunk
    nl, nr, cap = 4096, 512, 2048
    assert live_chunk(nl) == live_chunk(cap) == 1024

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    text = join._capped_inner_kernel.lower(
        (shape(nl + nr, jnp.int64),), shape(nl, jnp.bool_),
        shape(nr, jnp.bool_), n_ops=1, nl=nl, row_cap=cap).compile().as_text()
    names = _op_names(text, "sort")
    kernel = "jit(_capped_inner_kernel)/"
    assert text.count(" conditional(") == 1
    assert sorted(names) == [kernel + "cond/branch_0_fun/sort"] * 2 \
        + [kernel + "cond/branch_1_fun/sort", kernel + "sort"]
    expand = kernel + "cond/branch_0_fun/jit(_expand)/while"
    assert _op_names(text, "while").count(expand) == 3
    assert _op_names(text, "scatter") == [expand + "/body/scatter"]
    assert expand + "/body/jit(_take)/gather" in text
    # the many-to-one tail's one gather runs in chunks over the live rows
    assert kernel + "cond/branch_1_fun/while/body/jit(_take)/gather" in text
    assert set(_gather_slots(text)) == {1024}


def _capped_pallas_join_lowered(one_chip, kinds):
    """`inner_join_capped_pallas` over keys of `kinds`, 8,192 rows against
    512 under a cap of 4,096 and an alive mask, lowered for the described
    chip with its kernels for Mosaic."""
    from spark_rapids_tpu.ops import join_pallas
    nl, nr, cap = 8192, 512, 4096

    def columns(n):
        return [Column(dtype=d, length=n, data=jax.ShapeDtypeStruct(
            (n,), d.storage_dtype(), sharding=one_chip)) for d in kinds]
    return jax.jit(lambda l, r, alive: join_pallas.inner_join_capped_pallas(
        l, r, cap, lalive=alive, interpret=False)).lower(
            columns(nl), columns(nr),
            jax.ShapeDtypeStruct((nl,), jnp.bool_, sharding=one_chip))


def test_pallas_capped_join_expands_in_loops_for_v5e(one_chip,
                                                     no_persistent_cache):
    """The Pallas capped join whole (ops/join_pallas.py), its kernels
    compiled by Mosaic: between the count pass and the emit pass the
    expansion is the shared one, a scatter in a loop over the left rows
    that emit and ONE loop that gathers `starts` and both probe planes
    over the live slots. No gather runs at the cap."""
    from spark_rapids_tpu.ops.gather import live_chunk
    assert live_chunk(8192) == live_chunk(4096) == 1024
    text = _capped_pallas_join_lowered(one_chip, [dtypes.INT64]) \
        .compile().as_text()
    assert len(_op_names(text, "while")) == 2
    assert [n.split("/", 1)[1] for n in _op_names(text, "scatter")] == \
        ["jit(_emit_rows)/while/body/scatter"]
    assert _gather_slots(text) == [1024] * 3
    assert sum("pallas_hash_join_probe/pallas_call" in n
               for n in _op_names(text, "custom-call")) == 2


Q3_SHARE_ROWS = 2_250_000   # `q3.share`: the resident batch's rows


def test_eager_pallas_join_programs_compile_for_v5e(one_chip,
                                                    no_persistent_cache):
    """The eager entry's two programs (ops/join_pallas.py: `_count_matches`,
    `_emit_matches`) whole, at `q3.share`'s frame against the filtered
    dates: the build and the count kernel in the first, the expansion's
    two loops and the emit kernel in the second, under the names
    `pallas_join_bw_share` reads them by."""
    from spark_rapids_tpu.ops import join_pallas

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    left = [Column(dtype=dtypes.INT64, length=Q3_SHARE_ROWS,
                   data=shape(Q3_SHARE_ROWS, jnp.int64),
                   validity=shape(Q3_SHARE_ROWS, jnp.bool_))]
    right = [Column(dtype=dtypes.INT64, length=N_DATES_KEPT,
                    data=shape(N_DATES_KEPT, jnp.int64))]
    count = join_pallas._count_matches.lower(left, right, interpret=False)
    counts, planes, tbl, _ = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        count.out_info)
    emit = join_pallas._emit_matches.lower(
        counts, planes, tbl, total=Q3_SHARE_ROWS // 12, layout=(8,),
        interpret=False)
    count, emit = count.compile().as_text(), emit.compile().as_text()
    assert [n for n in _op_names(count, "custom-call") if "pallas" in n] == [
        "jit(_count_matches)/pallas_hash_join_build/pallas_call",
        "jit(_count_matches)/pallas_hash_join_probe/pallas_call"]
    assert len(_op_names(emit, "while")) == 2
    assert sum("pallas_hash_join_probe/pallas_call" in n
               for n in _op_names(emit, "custom-call")) == 1


def _without_locations(text):
    """A lowered program's text with every Mosaic payload (bytecode that
    carries its callers' file names and line numbers, so it differs from
    checkout to checkout) replaced by the kernel's own text without them."""
    from jax._src.lib.mlir import ir

    def kernel(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            body = ir.Module.parse(base64.b64decode(m.group(2)))
            return m.group(1) + body.operation.get_asm(
                enable_debug_info=False) + m.group(3)
    return re.sub(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)', kernel,
                  text)


# the capped Pallas join's program as 5d88d9f (PR 38) lowers it, before the
# eager entry became two jitted programs (PR 40): StableHLO ops, and the
# digest of the text with its kernels' source locations stripped
PALLAS_CAPPED_JOIN_AT_PR38 = {
    "int64_key": (272, "77315d7f3d6ee662394958c9c9ce9fb6c79333277cb64473"
                       "bf16bcfd574b170d"),
    "int32_and_int64_keys": (294, "5d270b4ecacd9344c592cc076161673df3db1e6e"
                                  "389a5138d2a9349c35251cad"),
}


@pytest.mark.parametrize("layout", sorted(PALLAS_CAPPED_JOIN_AT_PR38))
def test_pallas_capped_join_lowers_to_the_text_it_had(one_chip, layout):
    """`inner_join_capped_pallas` shares its helpers and kernel bodies with
    the eager entry. A change meant for the eager entry alone leaves this
    program's text as it was: a program whose text changes is a cold
    compile of `q72.tasks` (470-500 s on the chip host). Whoever changes
    the capped join on purpose pins the new text here."""
    kinds = {"int64_key": [dtypes.INT64],
             "int32_and_int64_keys": [dtypes.INT32, dtypes.INT64]}[layout]
    text = _capped_pallas_join_lowered(one_chip, kinds).as_text()
    assert re.findall(r"pallas_hash_join_[a-z]+", text) == [
        "pallas_hash_join_build", "pallas_hash_join_probe",
        "pallas_hash_join_probe"]
    ops, digest = PALLAS_CAPPED_JOIN_AT_PR38[layout]
    assert len(re.findall(r"stablehlo\.\w+", text)) == ops
    assert hashlib.sha256(
        _without_locations(text).encode()).hexdigest() == digest


Q18_LINES = 59_998_501      # `q18.batch`: lineitem's rows at SF10


@pytest.mark.parametrize("layout", ["int64_key", "int32_and_int64_keys"])
def test_lookup_membership_compiles_for_v5e(one_chip, no_persistent_cache,
                                            layout):
    """Step 1 of the eager joins' small-side path (ops/join_lookup.py) at
    the padded small side against `lineitem`'s rows at SF10: ONE loop whose
    body compares a chunk of small keys in one fusion over the frame, the
    one sort of the program is the small side's, and beside its arguments
    and the mask it needs the frame's 32-bit key words and the loop's mask,
    never a frame's sort buffers (the 60 M-row span kernel it replaces
    needed 2.5 GB, PERF.md, PR 34)."""
    from spark_rapids_tpu.ops import join_lookup
    kinds = [jnp.int64] if layout == "int64_key" else [jnp.int32, jnp.int64]
    words = sum(jnp.dtype(k).itemsize // 4 for k in kinds)

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    small = join_lookup.LOOKUP_SMALL
    compiled = join_lookup._member.lower(
        [shape(small, k) for k in kinds], shape(small, jnp.bool_),
        [shape(Q18_LINES, k) for k in kinds], [shape(Q18_LINES, jnp.bool_)]
    ).compile()
    text = compiled.as_text()
    assert len(_op_names(text, "while")) == 1
    sorted_rows = [int(re.search(r"\[(\d+)", line.split(" sort(")[0]).group(1))
                   for line in text.splitlines() if " sort(" in line]
    assert sorted_rows == [small]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (4 * words + 2) * (Q18_LINES + (1 << 20)), temp


Q13_ORDERS = 15_000_000     # `q13.batch`: orders' rows at SF10,
Q13_KEPT = 14_834_663       # and those its filter keeps


def test_compaction_sort_compiles_for_v5e(one_chip, no_persistent_cache):
    """The eager filter's sort path (ops/gather.py:rows_by_sort) at
    `q13.batch`'s own size, 15,000,000 rows with two int64 columns riding:
    five 32-bit words a row with the key. ONE sort of the frame, not a
    stable one (for which the compiler adds the row numbers as a sixth
    operand), five operands after the 64-bit split, and beside the
    arguments and the result (cut to the kept rows inside the program) the
    sort's own buffers: no second copy of the frame. Compiles in about
    100 s here, whatever the row count (60 s at 65,536 rows)."""
    from spark_rapids_tpu.ops import gather

    def shape(dtype):
        return jax.ShapeDtypeStruct((Q13_ORDERS,), dtype, sharding=one_chip)
    arrays = [shape(jnp.int64), shape(jnp.int64)]
    words = gather.plane_words(arrays)
    assert words == (2, 2)
    compiled = gather.rows_by_sort.lower(
        shape(jnp.bool_), arrays, kept=Q13_KEPT,
        groups=gather.ride_groups(words)).compile()
    sorts = [line for line in compiled.as_text().splitlines()
             if " sort(" in line]
    assert len(sorts) == 1 and "is_stable=true" not in sorts[0]
    assert sorts[0].split(" sort(")[0].count(f"[{Q13_ORDERS}]") == 5
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes < 20 * Q13_KEPT + (1 << 20)
    assert mem.temp_size_in_bytes < 21 * Q13_ORDERS, mem.temp_size_in_bytes


Q97_STORE = 36_000_000      # `q97.batch`: one rank's store_sales rows


def test_lookup_match_compiles_for_v5e(one_chip, no_persistent_cache):
    """The second pass of a broadcast join in which many rows pass
    (ops/join_lookup.py:_match, PR 43) at `q97.batch`'s store side: ONE
    loop over the frame, the small side's two sorts (the packing and the
    distinctness check) and no sort of the frame; beside the arguments and
    the (n,) int32 result it needs the frame's key words and the loop's
    carry, never a frame's sort buffers."""
    from spark_rapids_tpu.ops import join_lookup

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    small = join_lookup.LOOKUP_SMALL
    compiled = join_lookup._match.lower(
        [shape(small, jnp.int64)], shape(small, jnp.bool_),
        [shape(Q97_STORE, jnp.int64)], shape(Q97_STORE, jnp.bool_)).compile()
    text = compiled.as_text()
    assert len(_op_names(text, "while")) == 1
    sorted_rows = [int(re.search(r"\[(\d+)", line.split(" sort(")[0]).group(1))
                   for line in text.splitlines() if " sort(" in line]
    assert sorted_rows and set(sorted_rows) == {small}
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (4 * 2 + 4 + 2) * (Q97_STORE + (1 << 20)), temp


def test_eager_full_join_on_a_two_column_key_compiles_for_v5e(
        one_chip, no_persistent_cache):
    """The eager `full_outer` join's ONE kernel
    (`ops/join.py:_full_join_kernel`, what `full_join_counted` runs: both
    sides' answers off one union sort) over a two-column NULLABLE int64
    key at 65,536 + 32,768 rows. Nulls match nothing, so the keys bring no
    null-rank operand: two int64 key operands, the row number as the third
    key of a sort that is NOT a stable one, one flag payload; then the
    routing sort and the matchable right rows' packing, neither stable. A
    sort program's compile time follows its keys and whether it is stable,
    not its rows: about 100 s here for these three sorts, where the left
    pass of the stable form (four key operands, `_join_kernel`) took 220 s
    and the swapped anti pass as much again (`q97.batch` runs it at
    6,597,944 + 3,350,369 rows: 164 s here where the two passes took over
    1,000; PERF.md section 6, PR 43)."""
    from spark_rapids_tpu.ops import join
    nl, nr = 1 << 16, 1 << 15

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    lkeys = [Column(dtype=dtypes.INT64, length=8,
                    data=jnp.zeros((8,), jnp.int64),
                    validity=jnp.ones((8,), bool)) for _ in range(2)]
    per_key = jax.eval_shape(
        lambda: join._union_operands(lkeys, lkeys, False, None, None,
                                     ranked=False)[0])
    assert [str(o.dtype) for o in per_key] == ["int64", "int64"]
    operands = tuple(shape(nl + nr, o.dtype) for o in per_key)
    compiled = join._full_join_kernel.lower(
        operands, shape(nl, jnp.bool_), shape(nr, jnp.bool_),
        n_ops=len(operands), nl=nl).compile()
    sorts = [line for line in compiled.as_text().splitlines()
             if " sort(" in line]
    # the union sort, the routing sort, the matchable right rows' packing
    assert len(sorts) == 3
    assert not any("is_stable=true" in line for line in sorts)
    # counts, lo, rorder and the right rows' flag: three int32 planes, the
    # third of the frame, and a byte a right row
    out = compiled.memory_analysis().output_size_in_bytes
    assert out < 4 * (2 * nl + nl + nr) + nr + (1 << 16), out


Q97_PAIRS = (6_597_944, 3_350_369)      # `q97.batch`: distinct (customer,
Q97_MATCHED = 440                       # item) pairs a side, pairs in both


def test_sparse_join_output_compiles_for_v5e(one_chip, no_persistent_cache):
    """The right side of `q97.batch`'s full join over the left join's
    slots (`ops/gather.py:_write_rows`, PR 44): 440 matched slots of
    6,597,944, two int64 key columns and their masks. One program with no
    sort and no gather over the frame: the matched slots' positions, four
    gathers of 440 rows, five scatters into zero frames; beside the
    frames it hands back it holds a table a 32nd of the mask."""
    from spark_rapids_tpu.ops import gather
    slots, rows = Q97_PAIRS

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    compiled = gather._write_rows.lower(
        shape(slots, jnp.int32),
        [shape(rows, jnp.int64), shape(rows, jnp.bool_)] * 2,
        kept=Q97_MATCHED).compile()
    text = compiled.as_text()
    assert " sort(" not in text
    gathered = [int(n) for n in re.findall(
        r"= \w+\[(\d+)[\],][^=]* gather\(", text)]
    assert gathered and max(gathered) <= Q97_MATCHED, gathered
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes < ((8 + 1) * 2 + 1) * slots + (1 << 16)
    assert memory.temp_size_in_bytes < slots, memory.temp_size_in_bytes


Q13_ROWS = (1_500_000, 14_834_663)      # `q13.batch`: customers, the orders
Q13_SLOTS = 15_334_665                  # that pass the filter; output slots


def test_right_slots_compile_without_a_frame_long_gather_for_v5e(
        one_chip, no_persistent_cache):
    """The right map's inverse for `q13.batch`'s outer join
    (`ops/join.py:_expand_slots`, PR 45): the left map by `expand_rows`,
    the right rows' slots by chunked writes over the packed ranks, a
    32-bit running sum and ONE two-word sort by `rorder`. No gather is as
    long as a frame (the chunks' are a 64th of the left rows), and the
    program's code, which lies in HBM beside a peak with 15 MB of room
    under its bound, stays under what it was compiled at (13.0 MB; the
    same writes as two flat scatters were 24 MB, a 64-bit running maximum
    18.5 MB alone)."""
    from spark_rapids_tpu.ops import join
    nl, nr = Q13_ROWS

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    compiled = join._expand_slots.lower(
        shape(nl, jnp.int32), shape(nl, jnp.int32), shape(nl + nr, jnp.int32),
        shape(nr, jnp.bool_), total=Q13_SLOTS, holes=False).compile()
    text = compiled.as_text()
    # (a chunk's writes are a sort of that chunk inside the loop)
    assert [n for n in _op_names(text, "sort") if "while/body" not in n] \
        == ["jit(_expand_slots)/sort"]
    assert max(_gather_slots(text), default=0) < nl // 32
    memory = compiled.memory_analysis()
    assert memory.generated_code_size_in_bytes < 15 << 20
    # the left map, a slot a right row, a slot a left row; nothing else
    assert memory.output_size_in_bytes < 4 * (Q13_SLOTS + nr + nl) + (1 << 16)


def test_rows_by_slot_holds_one_frame_beside_its_results_for_v5e(
        one_chip, no_persistent_cache):
    """`q13.batch`'s destination sort at its real operands
    (`ops/gather.py:rows_by_slot`, PR 45: `o_custkey` and `o_orderkey`,
    int64 and without masks, the right rows' slots and a placeholder per
    customer; `ride_groups` gives one group): the 16,334,663-row frame of
    the key and the planes is the program's only temporary (12 bytes a
    row; the concatenations are not a second copy, and sorting the two
    columns in two groups on the same key compiles to the same buffers:
    196.1 MB either way), the results are cut to the slots inside the
    program, and its code stays under what it was compiled at (8.7 MB)."""
    from spark_rapids_tpu.ops import gather
    nl, nr = Q13_ROWS

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    arrays = [shape(nr, jnp.int64), shape(nr, jnp.int64)]
    groups = gather.ride_groups(gather.plane_words(arrays))
    assert groups == ((0, 1),)
    compiled = gather.rows_by_slot.lower(
        shape(nr, jnp.int32), shape(nl, jnp.int32), arrays,
        slots=Q13_SLOTS, groups=groups).compile()
    assert len(_op_names(compiled.as_text(), "sort")) == 1
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 13 * (nl + nr), \
        memory.temp_size_in_bytes
    assert memory.output_size_in_bytes < (8 + 8 + 1) * Q13_SLOTS + (1 << 16)
    assert memory.generated_code_size_in_bytes < 10 << 20


def _q51_batch() -> dict:
    """`q51.batch`'s stated counts (the configuration's fixed draw)."""
    import json
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "chipbench", "configs",
                           "nds-q51-sf100.json")) as f:
        return json.load(f)["batches"]["share"]


# `q51.batch`'s three windows: (rows, operands' dtypes, planes' dtypes,
# n_part_ops, fns, the kernel's form). A channel's running sum reads a
# sorted group-by's output on its own keys (`presorted`: the item and its
# null rank, the day); the running maxima above the full join sort by one
# packed key (two nullable int64 keys: four operands) with both totals
# and their masks riding
Q51_WINDOWS = {
    "store_sum": ("store_groups", ("int32", "int64", "int64"),
                  ("int64", "int8"), 2, (("sum", 0, 1, None),), "presorted"),
    "web_sum": ("web_groups", ("int32", "int64", "int64"),
                ("int64", "int8"), 2, (("sum", 0, 1, None),), "presorted"),
    "both_max": ("join_rows", ("int32", "int64", "int32", "int64"),
                 ("int64", "int8", "int64", "int8"), 2,
                 (("max", 0, 1, None), ("max", 2, 3, None)), "packed"),
}


@pytest.mark.parametrize("window", sorted(Q51_WINDOWS))
def test_window_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                        window):
    """`ops/window.py:_window_kernel` at the shapes of `q51.batch`'s three
    windows (PR 47). Over a group-by's output the program holds NO sort
    and no gather: two-level scans alone. Above the full join it holds ONE
    sort, not stable, on ONE key (the packed word), with the child's six
    words riding, and no gather as long as a frame; its temporaries are a
    few frames of the key and the planes, and its code, which lies in HBM,
    stays under what it was compiled at (77.2 MB: four 64-bit running
    maxima are 18.5 MB each, PR 45; the sort by its five key OPERANDS was
    124.9 MB of code and took 494 s to compile here, this takes 105)."""
    from spark_rapids_tpu.ops import window as window_ops
    rows, operands, planes, n_part_ops, fns, form = Q51_WINDOWS[window]
    n = int(_q51_batch()[rows])
    assert n > 1_000_000, "the configuration states the cell's counts"

    def shape(dtype, dims=(n,)):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dtype), sharding=one_chip)
    packing = None
    if form == "packed":
        k = len(operands)
        packing = (shape("int64", (k,)),) * 3 + (shape("int64", ()),) * 2
    compiled = window_ops._window_kernel.lower(
        tuple(shape(d) for d in operands), tuple(shape(d) for d in planes),
        packing, n_part_ops=n_part_ops, fns=fns,
        presorted=form == "presorted").compile()
    text = compiled.as_text()
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert max(_gather_slots(text), default=0) < n // 32
    memory = compiled.memory_analysis()
    plane_bytes = sum(jnp.dtype(d).itemsize for d in planes)
    if form == "presorted":
        assert not sorts
        assert memory.generated_code_size_in_bytes < 40 << 20
        # the sum, its count and the partitions: a few int64 frames
        assert memory.temp_size_in_bytes < 64 * n, memory.temp_size_in_bytes
    else:
        assert len(sorts) == 1 and "is_stable=true" not in sorts[0]
        # one key: the comparator reads two parameters
        assert "dimensions={0}" in sorts[0]
        assert memory.generated_code_size_in_bytes < 90 << 20
        assert memory.temp_size_in_bytes < 6 * (8 + plane_bytes) * n, \
            memory.temp_size_in_bytes


# sha256 (first 16 hex digits) of `_groupby_kernel.lower(...).as_text()` at
# the shapes of the cells that run it, with `ride_keys` as the cells run it
# since PR 48 (every key operand of these four rides the compaction sort,
# which is keyed on the start's position and not stable; the row number
# rides in none). Taken on PR 48's tree: a later PR that means to leave
# the group-by's programs alone holds them to these. (Compiling them for
# a described v5e takes 3 to 6 minutes each; tier-1 holds the text.)
GROUPBY_TEXT_AT_PR48 = {
    "q18.batch": "c941b114f2a13391",        # 60 M rows, decimal planes
    "q97.batch": "6df180c8cb8d9fcd",        # a DISTINCT, two nullable keys
    "q13.batch/count": "83957acb55f1999a",  # count of a nullable column
    "q13.batch/size": "b248de870018fbe1",
}


def _shape(n, dtype):
    return jax.ShapeDtypeStruct((n,), jnp.dtype(dtype))


def _groupby_lowering(cell: str):
    """`_groupby_kernel` lowered at an eager cell's shape, its keys riding
    as `ops/aggregate.py:_groupby` has them ride there (integer keys on
    frames over `KEPT_FLOOR` rows: every key operand)."""
    from spark_rapids_tpu.ops import aggregate
    if cell == "q18.batch":
        n = 59_986_052
        return aggregate._groupby_kernel.lower(
            (_shape(n, "int64"),),
            (_shape(n, "uint32"), _shape(n, "int32"), _shape(n, "int8")),
            (None, None, None), n_ops=1, agg_kinds=("sum", "sum", "count"),
            has_valids=(False,) * 3, has_alive=False, gather_payloads=True,
            ride_keys=(0,))
    if cell == "q97.batch":
        n = 6_874_157
        ops = tuple(_shape(n, d) for d in ("int32", "int64") * 2)
        return aggregate._groupby_kernel.lower(
            ops, (), (), n_ops=4, agg_kinds=(), has_valids=(),
            has_alive=False, gather_payloads=False, ride_keys=(0, 1, 2, 3))
    if cell == "q51.batch":
        # (item, d_date), neither nullable (the item's mask stays behind
        # its `IS NOT NULL` filter), a nullable int64 sum riding
        n = int(_q51_batch()["store_date_rows"])
        return aggregate._groupby_kernel.lower(
            (_shape(n, "int64"), _shape(n, "int64")), (_shape(n, "int64"),),
            (_shape(n, "bool"),), n_ops=2, agg_kinds=("sum",),
            has_valids=(True,), has_alive=False, gather_payloads=False,
            ride_keys=(0, 1))
    if cell == "q13.batch/count":
        n = 15_334_665
        return aggregate._groupby_kernel.lower(
            (_shape(n, "int64"),), (_shape(n, "int8"),),
            (_shape(n, "bool"),), n_ops=1, agg_kinds=("count",),
            has_valids=(True,), has_alive=False, gather_payloads=False,
            ride_keys=(0,))
    n = 1_500_000
    return aggregate._groupby_kernel.lower(
        (_shape(n, "int64"),), (_shape(n, "int8"),), (None,), n_ops=1,
        agg_kinds=("size",), has_valids=(False,), has_alive=False,
        gather_payloads=False, ride_keys=(0,))


@pytest.mark.parametrize("cell", sorted(GROUPBY_TEXT_AT_PR48))
def test_groupby_kernel_lowers_to_the_text_it_had(cell):
    text = _groupby_lowering(cell).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == GROUPBY_TEXT_AT_PR48[cell]


def _lowered_sorts(text: str):
    """-> [(stable, 32-bit words a row over its operands)] of the sorts in
    a lowered (StableHLO) text, in order: a 64-bit operand is two words
    (the chip's compiler splits it), anything narrower one."""
    out = []
    for stable, args in re.findall(
            r'"stablehlo\.sort"\(.*?is_stable = (\w+)\}> \(\{\s*'
            r'\^bb0\((.*?)\):', text, flags=re.S):
        bits = [int(b) for b in re.findall(r"tensor<[a-z]+(\d+)>", args)]
        assert len(bits) % 2 == 0       # the comparator sees each twice
        out.append((stable == "true", sum(max(b // 32, 1) for b in bits) // 2))
    return out


def test_q51_keyed_sum_lowers_through_the_shared_sort(one_chip):
    """`q51.batch`'s keyed sums (6.9 M and 1.7 M rows into (item, day)
    groups, a nullable int64 sum riding) are `_groupby_kernel` as split:
    two sorts, the key sort with the value and its mask riding, a stable
    one as every cell's group-by with an aggregate still has it (ROADMAP
    S6 (e)), and the compaction sort, which since PR 48 is not; the run
    flags by `run_boundaries`."""
    text = _groupby_lowering("q51.batch").as_text()
    sorts = re.findall(r"stablehlo\.sort.*?is_stable = (\w+)", text,
                       flags=re.S)
    assert sorts == ["true", "false"]


@pytest.mark.parametrize("cell,words", [
    ("q18.batch", 7),           # position; the int64 key; two int64 sums
    ("q13.batch/count", 4),     # position; the int64 key; the count
    ("q97.batch", 7),           # position; a rank and an int64, twice
    ("q51.batch", 8),           # position; two int64 keys; count; sum
])
def test_compaction_sort_carries_the_keys_in_the_row_numbers_place(cell,
                                                                   words):
    """The sorted group-by's compaction sort at each eager cell's shape
    (PR 48): ONE key, the start's position, not stable; the groups' key
    operands ride it and neither a `flag` operand nor the first rows'
    numbers do, so `q18.batch` (whose peak memory is this sort's operands
    in and out, 0.24 GB a word a side) and `q13.batch` carry the words
    they carried. (ISSUE 48 reckoned nine for `q51.batch`: its date key
    comes from `date_dim` without a mask, so no rank rides.)"""
    *_, (stable, got) = _lowered_sorts(_groupby_lowering(cell).as_text())
    assert (stable, got) == (False, words)


def test_capped_groupby_at_q3_tasks_shape_lowers_without_key_words(
        monkeypatch):
    """The capped tier's keyed aggregate at `q3.tasks`' shape (a frame of
    360,000 rows, a key cap of 8,192, two int64 keys and a live mask):
    16,384 gathered key slots are 0.25 ms, three words riding over
    360,000 rows 1 ms (`ops/gather.py:words_ride`), so its compaction
    sort carries the position, the first rows' numbers and the sum, and
    no key word; the keys are gathered over the cap's slots."""
    from spark_rapids_tpu.ops import aggregate
    monkeypatch.setenv("SPARK_RAPIDS_TPU_GROUPBY_KERNEL", "scan")
    n, cap = 360_000, 8_192
    t = Table([_i64(n), _i64(n), _i64(n)], names=["y", "b", "v"])

    def capped(t, alive):
        return aggregate.groupby_aggregate_capped(
            t, ["y", "b"], [("v", "sum")], key_cap=cap, alive=alive)
    with aggregate.group_keys.collect() as did:
        text = jax.jit(capped).lower(t, _shape(n, "bool")).as_text()
    assert did == [("take", 2 * cap)]
    (stable, keyed), (stable2, comp) = _lowered_sorts(text)
    assert (stable, stable2) == (True, False)
    assert comp == 4            # position, first row, the int64 sum
    # `jnp.take` lowers to one private function, called for each key
    takes = re.findall(r"call @_take\w*\(.*?-> tensor<(\d+)xi64>", text)
    assert takes == [str(cap)] * 2
