"""Histogram creation and percentile evaluation (Spark approx-percentile
final-evaluation path).

Reference: /root/reference/src/main/cpp/src/histogram.cu —
create_histogram_if_valid (:282: frequencies must be non-null INT64 with no
negatives; zero-frequency rows turn into nulls / empty lists; null values
get frequency 1 so downstream MERGE_HISTOGRAM never sees zero counts) and
percentile_from_histogram (:428: per-histogram sort ascending nulls-last,
segmented prefix-sum of counts, linear interpolation between the bounding
elements — fill_percentile_fn :53), Java facade Histogram.java:47-68.

TPU-native design: one flattened lexsort over (label, is_null, value)
replaces the segmented sort; the per-(histogram, percentage) lower_bound is
a segment-sum of `count < target` indicators (no per-row binary search);
interpolation keeps the reference's two-term formula for identical
round-off.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar.column import Column

_ARITH_KINDS = {
    dtypes.Kind.INT8, dtypes.Kind.INT16, dtypes.Kind.INT32, dtypes.Kind.INT64,
    dtypes.Kind.FLOAT32, dtypes.Kind.FLOAT64, dtypes.Kind.BOOL,
    dtypes.Kind.UINT8,
}


def create_histogram_if_valid(values: Column, frequencies: Column,
                              output_as_lists: bool) -> Column:
    """Pair (values, frequencies) into STRUCT<value, freq> histogram rows
    (histogram.cu:282)."""
    if frequencies.dtype.kind != dtypes.Kind.INT64:
        raise TypeError("frequencies must be INT64")
    if frequencies.has_nulls():
        raise ValueError("frequencies must not have nulls")
    if values.length != frequencies.length:
        raise ValueError("values and frequencies must have the same size")
    freqs = frequencies.data
    n = values.length
    if n and int(jnp.min(freqs)) < 0:
        raise ValueError("frequencies must not contain negative values")
    positive = freqs > 0
    any_zero = n > 0 and not bool(jnp.all(positive))

    if output_as_lists:
        # zero-frequency rows become empty lists; struct children unchanged
        sizes = positive.astype(jnp.int32) if any_zero else \
            jnp.ones((n,), jnp.int32)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(sizes)]).astype(jnp.int32)
        if any_zero:
            keep = np.flatnonzero(np.asarray(positive))
            child_vals = Column(
                dtype=values.dtype, length=len(keep),
                data=jnp.take(values.data, jnp.asarray(keep), axis=0),
                validity=(jnp.take(values.null_mask, jnp.asarray(keep))
                          if values.validity is not None else None))
            child_freqs = Column.from_numpy(
                np.asarray(jnp.take(freqs, jnp.asarray(keep))), dtypes.INT64)
        else:
            child_vals = values
            child_freqs = frequencies
        struct = Column.make_struct(value=child_vals, freq=child_freqs)
        return Column.make_list(offsets, struct)

    # struct output. Only when zero frequencies exist (histogram.cu:345
    # null_count > 0 guard): zero-frequency rows nullify the value, and all
    # null rows — pre-existing included — get frequency 1 (:362-375). With
    # all-positive frequencies the input passes through untouched (:416-418).
    if not any_zero:
        return Column.make_struct(value=values, freq=frequencies)
    new_valid = values.null_mask & positive
    out_freqs = jnp.where(new_valid, freqs, jnp.int64(1))
    out_vals = Column(dtype=values.dtype, length=n, data=values.data,
                      validity=new_valid)
    return Column.make_struct(
        value=out_vals,
        freq=Column(dtype=dtypes.INT64, length=n, data=out_freqs))


def percentile_from_histogram(input_col: Column,
                              percentages: Sequence[float],
                              output_as_list: bool) -> Column:
    """Evaluate percentiles over LIST<STRUCT<value, freq:int64>> histograms
    (histogram.cu:428)."""
    if input_col.dtype.kind != dtypes.Kind.LIST:
        raise TypeError("input must be a LIST column")
    struct = input_col.children[0]
    if struct.dtype.kind != dtypes.Kind.STRUCT or len(struct.children) != 2:
        raise TypeError("child must be STRUCT with two children")
    if struct.has_nulls():
        raise ValueError("child of the input column must not have nulls")
    data_col, counts_col = struct.children
    if counts_col.dtype.kind != dtypes.Kind.INT64:
        raise TypeError("counts must be INT64")
    if counts_col.has_nulls():
        raise ValueError("counts must not have nulls")
    if data_col.dtype.kind not in _ARITH_KINDS:
        raise TypeError(f"unsupported histogram value type {data_col.dtype}")

    n_hist = input_col.length
    n_pct = len(percentages)
    pct = jnp.asarray(np.asarray(percentages, np.float64))
    offsets = input_col.offsets.astype(jnp.int32)
    m = data_col.length

    if m == 0 or n_hist == 0:
        # every histogram is empty -> every output row is null (the main
        # path's ALL_NULL handling, histogram.cu:176-184)
        if output_as_list:
            lo = jnp.zeros((n_hist + 1,), jnp.int32)
            child = Column(dtype=dtypes.FLOAT64, length=0,
                           data=jnp.zeros((0,), jnp.float64))
            return Column.make_list(lo, child,
                                    validity=jnp.zeros((n_hist,), jnp.bool_))
        return Column(dtype=dtypes.FLOAT64, length=n_hist * n_pct,
                      data=jnp.zeros((n_hist * n_pct,), jnp.float64),
                      validity=jnp.zeros((n_hist * n_pct,), jnp.bool_))

    out_vals, out_valid = _percentile_kernel(
        data_col.data.astype(jnp.float64), data_col.null_mask,
        counts_col.data, offsets, pct, n_hist=n_hist)

    if output_as_list:
        # null histograms produce empty lists (purge_nonempty_nulls)
        sizes = jnp.where(out_valid, n_pct, 0).astype(jnp.int32)
        lo = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(sizes)]).astype(jnp.int32)
        flat = out_vals.reshape(-1)
        keepers = jnp.repeat(out_valid, n_pct)
        keep_idx = np.flatnonzero(np.asarray(keepers))
        child = Column(dtype=dtypes.FLOAT64, length=len(keep_idx),
                       data=jnp.take(flat, jnp.asarray(keep_idx)))
        return Column.make_list(
            lo, child,
            validity=None if bool(jnp.all(out_valid)) else out_valid)
    flat = out_vals.reshape(-1)
    valid = jnp.repeat(out_valid, n_pct)
    return Column(dtype=dtypes.FLOAT64, length=n_hist * n_pct, data=flat,
                  validity=None if bool(jnp.all(valid)) else valid)


@partial(jax.jit, static_argnames=("n_hist",))
def _percentile_kernel(values, valid, counts, offsets, pct, *, n_hist):
    m = values.shape[0]
    n_pct = pct.shape[0]
    labels = (jnp.searchsorted(offsets, jnp.arange(m, dtype=jnp.int32),
                               side="right") - 1).astype(jnp.int32)
    # segmented sort: by (histogram, nulls-last, value)
    order = jnp.lexsort((values, ~valid, labels))
    s_vals = values[order]
    s_valid = valid[order]
    s_counts = counts[order]
    s_labels = labels[order]
    # segmented inclusive prefix-sum of counts
    cum = jnp.cumsum(s_counts)
    seg_base = jnp.where(offsets[:-1] > 0, cum[jnp.maximum(offsets[:-1] - 1, 0)],
                         jnp.int64(0))
    acc = cum - seg_base[s_labels]

    start = offsets[:-1]
    try_end = offsets[1:]
    last_valid = s_valid[jnp.maximum(try_end - 1, 0)]
    end = jnp.where((try_end > start) & ~last_valid, try_end - 1, try_end)
    has_all_nulls = start >= end
    out_valid = ~has_all_nulls

    max_pos = jnp.where(has_all_nulls, jnp.int64(0),
                        acc[jnp.maximum(end - 1, 0)] - 1)
    position = max_pos[:, None].astype(jnp.float64) * pct[None, :]
    lower = jnp.floor(position).astype(jnp.int64)
    higher = jnp.ceil(position).astype(jnp.int64)

    def search(target):
        """start + count of acc[j] < target in [start, end) per histogram."""
        t_per_elem = target[s_labels, :]                      # (m, n_pct)
        ind = (acc[:, None] < t_per_elem) & \
            (jnp.arange(m)[:, None] >= start[s_labels][:, None]) & \
            (jnp.arange(m)[:, None] < end[s_labels][:, None])
        cnt = jax.ops.segment_sum(ind.astype(jnp.int32), s_labels,
                                  num_segments=n_hist)
        return start[:, None] + cnt

    lower_idx = search(lower + 1)
    higher_idx = search(higher + 1)
    safe = lambda i: jnp.clip(i, 0, m - 1)
    lo_el = s_vals[safe(lower_idx)]
    hi_el = s_vals[safe(higher_idx)]
    same = (higher == lower) | (hi_el == lo_el)
    lower_part = (higher.astype(jnp.float64) - position) * lo_el
    higher_part = (position - lower.astype(jnp.float64)) * hi_el
    out = jnp.where(same, lo_el, lower_part + higher_part)
    out = jnp.where(out_valid[:, None], out, jnp.float64(0))
    return out, out_valid
