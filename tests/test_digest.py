"""The result cache's input digest (serving/cache.py, docs/serving.md).

A Table's buffers are folded to 128 bits where they live; the host hashes
names, types, shapes and the folds. Held here exactly: equal contents give
equal keys (and a cache hit with the right answer), and every way two
tables can differ - one bit of one element of any buffer, the order of two
rows, a name, a dtype over the same bits, a shape over the same bytes -
gives another key.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import Column, Table, dtypes
from spark_rapids_tpu.plan import PlanBuilder, col
from spark_rapids_tpu.serving import ServingScheduler, cache_key
from spark_rapids_tpu.serving import cache as cache_mod

N = 257          # no multiple of a lane, a sublane or a word


def _buffers(seed=0):
    """Host arrays of one table that uses every kind of buffer a Column
    can hold: data, validity, offsets, a child, 2-D limbs."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, 6, N)
    offs = np.zeros(N + 1, np.int32)
    np.cumsum(lens, out=offs[1:])
    return {
        "k.data": rng.integers(-2**62, 2**62, N),
        "k.validity": rng.random(N) < 0.9,
        "v.data": rng.integers(1, 100, N),
        "f.data": rng.standard_normal(N),
        "s.data": rng.integers(97, 123, int(offs[-1])).astype(np.uint8),
        "s.offsets": offs,
        "s.validity": rng.random(N) < 0.8,
        "d.data": rng.integers(0, 2**32, (N, 4)).astype(np.uint32),
        "l.offsets": offs.copy(),
        "l.child": rng.integers(-2**31, 2**31, int(offs[-1])).astype(np.int32),
    }


def _build(b, names=("k", "v", "f", "s", "d", "l"), put=jnp.asarray):
    child = Column(dtypes.INT32, len(b["l.child"]), data=put(b["l.child"]))
    return Table([
        Column(dtypes.INT64, N, data=put(b["k.data"]),
               validity=put(b["k.validity"])),
        Column(dtypes.INT64, N, data=put(b["v.data"])),
        Column(dtypes.FLOAT64, N, data=put(b["f.data"])),
        Column(dtypes.STRING, N, data=put(b["s.data"]),
               offsets=put(b["s.offsets"]), validity=put(b["s.validity"])),
        Column(dtypes.decimal(38, 2), N, data=put(b["d.data"])),
        Column(dtypes.list_(dtypes.INT32), N, offsets=put(b["l.offsets"]),
               children=(child,)),
    ], names=list(names))


def _key(table):
    key = cache_key(_plan(), {"t": table})
    assert key is not None
    return key


def _plan():
    b = PlanBuilder()
    return (b.scan("t", schema=["k", "v"]).filter(col("v") > 10)
            .aggregate(["k"], [("v", "sum", "total")])
            .sort(["k"]).build())


def _flip(a, where, bit):
    """`a` with one bit of one element flipped."""
    a = a.copy()
    flat = a.reshape(-1)
    i = {"first": 0, "middle": flat.size // 2, "last": flat.size - 1}[where]
    if a.dtype == np.bool_:
        flat[i] = not flat[i]
    else:
        word = flat[i:i + 1].view(f"u{a.dtype.itemsize}")
        word ^= np.array(1, word.dtype) << np.array(bit, word.dtype)
    return a


def test_equal_contents_built_twice_give_one_key():
    assert _key(_build(_buffers())) == _key(_build(_buffers()))
    assert _key(_build(_buffers(0))) != _key(_build(_buffers(1)))


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("buffer", sorted(_buffers()))
def test_one_flipped_bit_changes_the_key(buffer, where):
    base = _buffers()
    width = 8 * base[buffer].dtype.itemsize
    for bit in sorted({0, width - 1}):      # the lowest and the highest
        changed = dict(base, **{buffer: _flip(base[buffer], where, bit)})
        assert _key(_build(changed)) != _key(_build(base)), (buffer, bit)


def test_one_flipped_bit_moves_every_lane():
    x = np.arange(N, dtype=np.int64)
    a = np.asarray(cache_mod._fold_buffers(cache_mod._SEEDS, x))
    b = np.asarray(cache_mod._fold_buffers(cache_mod._SEEDS,
                                           _flip(x, "middle", 40)))
    assert a.shape == (1, 4) and a.dtype == np.uint32
    assert (a != b).all()


def test_two_swapped_rows_change_the_key():
    base = _buffers()
    swapped = base["v.data"].copy()
    i, j = 3, N - 5
    assert swapped[i] != swapped[j]
    swapped[[i, j]] = swapped[[j, i]]
    assert _key(_build(dict(base, **{"v.data": swapped}))) \
        != _key(_build(base))


def test_position_counts_beyond_32_bits():
    seed, word = jnp.uint32(7), jnp.arange(4, dtype=jnp.uint32)
    lo = jnp.full(4, 5, jnp.uint32)
    near = cache_mod._mix(seed, (lo, jnp.zeros(4, jnp.uint32), word))
    far = cache_mod._mix(seed, (lo, jnp.ones(4, jnp.uint32), word))
    assert (np.asarray(near) != np.asarray(far)).all()


def test_a_renamed_column_changes_the_key():
    base = _buffers()
    assert _key(_build(base, names=("k", "v", "f", "s", "d", "m"))) \
        != _key(_build(base))


def test_another_dtype_over_the_same_bits_changes_the_key():
    bits = np.arange(1, N + 1, dtype=np.int64) << 52     # normal doubles
    as_int = Table([Column(dtypes.INT64, N, data=jnp.asarray(bits))], ["k"])
    as_float = Table([Column(dtypes.FLOAT64, N,
                             data=jnp.asarray(bits.view(np.float64)))], ["k"])
    as_time = Table([Column(dtypes.TIMESTAMP_US, N, data=jnp.asarray(bits))],
                    ["k"])
    assert len({_key(as_int), _key(as_float), _key(as_time)}) == 3


def test_another_shape_over_the_same_bytes_changes_the_key():
    limbs = _buffers()["d.data"]
    dec = dtypes.decimal(38, 2)
    a = Table([Column(dec, N, data=jnp.asarray(limbs))], ["d"])
    b = Table([Column(dec, N, data=jnp.asarray(limbs.reshape(2 * N, 2)))],
              ["d"])
    assert _key(a) != _key(b)


def test_no_validity_is_not_all_valid_but_is_itself():
    data = np.arange(N, dtype=np.int64)

    def table(validity):
        return Table([Column(dtypes.INT64, N, data=jnp.asarray(data),
                             validity=validity)], ["k"])
    assert _key(table(None)) == _key(table(None))
    # sound, not complete: an explicit all-true mask is another binding
    assert _key(table(None)) != _key(table(jnp.ones(N, jnp.bool_)))


@pytest.mark.parametrize("dtype", [dtypes.INT64, dtypes.STRING],
                         ids=["int64", "string"])
def test_zero_length_buffers_digest(dtype):
    def empty(dt):
        if dt.is_string:
            return Table([Column.from_pylist([], dt)], ["k"])
        return Table([Column(dt, 0, data=jnp.zeros(
            (0,), dt.storage_dtype()))], ["k"])
    assert _key(empty(dtype)) == _key(empty(dtype))
    assert _key(empty(dtype)) != _key(empty(dtypes.INT32))


def test_every_nan_payload_and_zero_sign_is_told_apart():
    bits = np.array([0x7FF8000000000000, 0x7FF8000000000001,
                     0x0000000000000000, 0x8000000000000000], np.uint64)

    def table(b):
        return Table([Column(dtypes.FLOAT64, len(b),
                             data=jnp.asarray(b.view(np.float64)))], ["f"])
    keys = {_key(table(np.roll(bits, r)[:2])) for r in range(4)}
    assert len(keys) == 4


def test_the_words_a_tpu_folds_for_float64_are_its_bits():
    """XLA:TPU lowers no f64 bitcast, so there the fold takes its words
    from arithmetic (`_f64_bits`); on normal values, zeros, infinities
    and the canonical NaN that is the same words the CPU's bitcast gives."""
    x = np.concatenate([np.random.default_rng(3).standard_normal(N) * 1e30,
                        [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -2.5e-300]])
    assert np.array_equal(np.asarray(cache_mod._f64_bits(jnp.asarray(x))),
                          x.view(np.uint64))


def test_a_host_resident_buffer_keys_like_its_device_copy():
    base = _buffers()
    on_host = _build(base, put=np.asarray)
    assert isinstance(on_host.columns[0].data, np.ndarray)
    on_device = _build(base, put=lambda a: jax.device_put(
        a, jax.devices()[-1]))
    assert _key(on_host) == _key(_build(base)) == _key(on_device)


def test_the_fold_is_keyed():
    x = np.arange(N, dtype=np.int64)
    other = cache_mod._SEEDS ^ np.uint32(1)
    assert cache_mod._SEEDS.shape == (4,) and cache_mod._SEEDS.dtype == np.uint32
    assert (np.asarray(cache_mod._fold_buffers(cache_mod._SEEDS, x))
            != np.asarray(cache_mod._fold_buffers(other, x))).all()


def test_equal_contents_hit_with_the_first_answer_and_a_flip_misses():
    plan, base = _plan(), _buffers()
    first, second = (_build(base).select(["k", "v"]) for _ in range(2))
    third = _build(dict(base, **{"v.data": _flip(base["v.data"], "last", 6)})
                   ).select(["k", "v"])
    with ServingScheduler(workers=1) as sched:
        s = sched.open_session("s")
        cold = s.run(plan, {"t": first}, timeout=120)
        assert not cold.cached
        ticket = s.submit(plan, {"t": second})
        hot = ticket.result(timeout=120)
        assert ticket.cached and hot.cached
        assert hot.table.to_pydict() == cold.table.to_pydict()
        fresh = s.run(plan, {"t": third}, timeout=120)
        assert not fresh.cached
        assert fresh.table.to_pydict() != cold.table.to_pydict()
        # keys stay in the process: neither report prints one
        printed = repr((sched.metrics(), sched.cache.stats()))
        assert cache_key(plan, {"t": first})[1] not in printed
