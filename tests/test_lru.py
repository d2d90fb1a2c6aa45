"""LruDict (utils/lru.py): the one bounded-cache definition shared by the
plan executor's program/caps memos and the optimizer's rewrite caches."""
from spark_rapids_tpu.utils import LruDict


def test_insert_evicts_oldest_in_order():
    d = LruDict(maxsize=3)
    for k in "abcd":
        d[k] = k.upper()
    assert list(d) == ["b", "c", "d"]          # "a" was the oldest
    d["e"] = "E"
    assert list(d) == ["c", "d", "e"]


def test_get_refreshes_recency():
    d = LruDict(maxsize=3)
    for k in "abc":
        d[k] = k.upper()
    assert d.get("a") == "A"                   # refresh: "a" now newest
    d["d"] = "D"
    assert "a" in d and "b" not in d           # "b" evicted instead
    assert list(d) == ["c", "a", "d"]


def test_get_miss_returns_default_without_insert():
    d = LruDict(maxsize=2)
    d["a"] = 1
    assert d.get("zz") is None
    assert d.get("zz", 7) == 7
    assert list(d) == ["a"]


def test_overwrite_refreshes_and_keeps_size():
    d = LruDict(maxsize=2)
    d["a"] = 1
    d["b"] = 2
    d["a"] = 10                                # overwrite = most recent
    d["c"] = 3
    assert list(d) == ["a", "c"] and d["a"] == 10


def test_plain_getitem_does_not_refresh():
    d = LruDict(maxsize=2)
    d["a"] = 1
    d["b"] = 2
    assert d["a"] == 1                         # dict semantics: no refresh
    d["c"] = 3
    assert "a" not in d                        # "a" was still the oldest


def test_discard_drops_an_entry_and_ignores_a_miss():
    d = LruDict(maxsize=2)
    d["a"] = 1
    d["b"] = 2
    d.discard("a")
    d.discard("zz")                            # not there: nothing happens
    d["c"] = 3                                 # room again: "b" stays
    assert list(d) == ["b", "c"]
