"""from_json: whole-column JSON -> MAP (LIST<STRUCT<key:string, value:string>>)
extracting *raw* key/value substrings.

Reference: /root/reference/src/main/cpp/src/map_utils.cu — unify rows
(:68-117, null rows read as "{}"), cudf FST tokenizer (:663), node
classification into keys/values (:359-388), raw substring ranges (string
nodes lose their quotes, nested object/array values keep their full text —
node_ranges_fn :397-482), gather + assemble (:519-731); golden expectations
in MapUtilsTest.java (e.g. "index": [4,{},null,{"a":[{ }, {}] } ] comes back
verbatim).

TPU-native design: instead of porting the FST, the kernel runs a 3-state
string-literal automaton (normal / in-string / escape) over the padded char
matrix with `lax.associative_scan` function-composition — the classic
parallel-FSM trick — then derives bracket depth by cumulative sum of
structural braces outside strings. Top-level colons/commas at depth 1 give
the pair boundaries; prefix/suffix scans provide whitespace trimming; one
flat gather materializes all key/value spans across the column at once.

Spark-facing behavior: null input rows -> null map rows; empty/whitespace
rows -> valid empty maps (the reference's "{}" fill); valid-JSON non-object
rows -> null map rows (Spark's PERMISSIVE null); structurally broken JSON
(unbalanced braces/quotes, missing colons/values, trailing content after
the object) raises like the reference's tokenizer error
(map_utils.cu:120-158).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..columnar.column import Column, _round_bucket, make_string_column

_WS = (ord(" "), ord("\t"), ord("\n"), ord("\r"))


@partial(jax.jit, static_argnames=("L",))
def _structure_kernel(chars, lens, *, L):
    """Per-position structural facts: string mask, bracket depth, and the
    top-level delimiter masks."""
    n = chars.shape[0]
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    live = pos < lens[:, None]
    c = jnp.where(live, chars, jnp.uint8(0))

    # ---- parallel 3-state FSM: 0 normal, 1 in-string, 2 escape ----------
    is_quote = c == ord('"')
    is_bslash = c == ord("\\")
    # per-char transition vector t[s] = next state if current state is s
    t0 = jnp.where(is_quote, 1, 0)
    t1 = jnp.where(is_quote, 0, jnp.where(is_bslash, 2, 1))
    t2 = jnp.ones_like(t0)
    trans = jnp.stack([t0, t1, t2], axis=-1).astype(jnp.int32)  # (n, L, 3)

    def compose(a, b):
        return jnp.take_along_axis(b, a, axis=-1)

    after = jax.lax.associative_scan(compose, trans, axis=1)
    state_after = after[:, :, 0]
    state_before = jnp.concatenate(
        [jnp.zeros((n, 1), jnp.int32), state_after[:, :-1]], axis=1)

    outside = state_before == 0
    open_b = ((c == ord("{")) | (c == ord("["))) & outside
    close_b = ((c == ord("}")) | (c == ord("]"))) & outside
    delta = open_b.astype(jnp.int32) - close_b.astype(jnp.int32)
    depth_after = jnp.cumsum(delta, axis=1)
    depth_before = depth_after - delta

    is_ws = jnp.isin(c, jnp.asarray(_WS, jnp.uint8)) | ~live
    nonws = ~is_ws & live

    # row shape checks
    first_nw = jnp.min(jnp.where(nonws, pos, L), axis=1)
    last_nw = jnp.max(jnp.where(nonws, pos, -1), axis=1)
    fc = jnp.take_along_axis(c, jnp.clip(first_nw, 0, L - 1)[:, None],
                             axis=1)[:, 0]
    lc = jnp.take_along_axis(c, jnp.clip(last_nw, 0, L - 1)[:, None],
                             axis=1)[:, 0]
    empty_row = first_nw >= L
    is_object = ~empty_row & (fc == ord("{")) & (lc == ord("}"))

    final_state = jnp.take_along_axis(
        state_after, jnp.clip(lens - 1, 0, L - 1)[:, None], axis=1)[:, 0]
    final_state = jnp.where(lens > 0, final_state, 0)
    final_depth = jnp.take_along_axis(
        depth_after, jnp.clip(lens - 1, 0, L - 1)[:, None], axis=1)[:, 0]
    final_depth = jnp.where(lens > 0, final_depth, 0)
    neg_depth = jnp.any(live & (depth_after < 0), axis=1)
    broken = (final_state != 0) | (final_depth != 0) | neg_depth

    top = depth_before == 1
    colon1 = (c == ord(":")) & outside & top
    comma1 = (c == ord(",")) & outside & top
    # a pair delimiter: the object's '{' or a top-level ','
    open_obj = (c == ord("{")) & outside & (depth_before == 0)
    close_obj = (c == ord("}")) & outside & (depth_after == 0)

    # structural sanity inside objects: an empty object has no content at
    # depth >= 1; otherwise n_colons == n_commas + 1
    nc = jnp.sum(colon1, axis=1)
    nm = jnp.sum(comma1, axis=1)
    has_content = jnp.any(nonws & (depth_before >= 1) & (depth_after >= 1),
                          axis=1)
    pair_broken = is_object & jnp.where(
        has_content, nc != nm + 1, (nc != 0) | (nm != 0))
    # trailing/multiple top-level values: an object row may have exactly one
    # top-level '{' and nothing else at depth 0
    top_junk = nonws & outside & (depth_before == 0) & (depth_after == 0)
    pair_broken |= is_object & (
        (jnp.sum(open_obj, axis=1) != 1) | jnp.any(top_junk, axis=1))

    # prev delimiter (inclusive) and next delimiter (exclusive) per position
    delim_prev = jnp.where(open_obj | comma1, pos, -1)
    prev_scan = jax.lax.associative_scan(jnp.maximum, delim_prev, axis=1)
    delim_next = jnp.where(close_obj | comma1, pos, L)
    next_scan = jax.lax.associative_scan(jnp.minimum, delim_next,
                                         reverse=True, axis=1)
    # nearest non-ws at or after / at or before each position
    nnw = jax.lax.associative_scan(jnp.minimum,
                                   jnp.where(nonws, pos, L),
                                   reverse=True, axis=1)
    pnw = jax.lax.associative_scan(jnp.maximum,
                                   jnp.where(nonws, pos, -1), axis=1)

    return dict(colon1=colon1, prev_scan=prev_scan, next_scan=next_scan,
                nnw=nnw, pnw=pnw, chars=c, broken=broken,
                pair_broken=pair_broken, is_object=is_object,
                n_pairs=nc.astype(jnp.int32), empty_row=empty_row)


def from_json(column: Column) -> Column:
    """String column of JSON objects -> LIST<STRUCT<key, value>> raw map
    (MapUtils.extractRawMapFromJsonString, map_utils.cu:649)."""
    if not column.dtype.is_string:
        raise TypeError("from_json expects a string column")
    n = column.length
    if n == 0:
        struct = Column.make_struct(
            key=Column.from_pylist([], dtypes.STRING),
            value=Column.from_pylist([], dtypes.STRING))
        return Column.make_list(jnp.zeros((1,), jnp.int32), struct)
    padded, lens = column.padded_chars()
    L = padded.shape[1]
    s = _structure_kernel(padded, lens, L=L)

    in_valid = column.null_mask
    broken = np.asarray(s["broken"] & in_valid)
    if broken.any():
        bad = int(np.flatnonzero(broken)[0])
        raise ValueError(f"invalid JSON in row {bad}: "
                         f"{column.to_pylist()[bad]!r}")
    pair_broken = np.asarray(s["pair_broken"] & in_valid)
    if pair_broken.any():
        bad = int(np.flatnonzero(pair_broken)[0])
        raise ValueError(f"malformed JSON object in row {bad}: "
                         f"{column.to_pylist()[bad]!r}")

    # rows contributing pairs: valid, object-shaped
    row_ok = np.asarray(in_valid & s["is_object"])
    n_pairs = np.where(row_ok, np.asarray(s["n_pairs"]), 0)
    offsets = np.zeros(n + 1, np.int32)
    np.cumsum(n_pairs, out=offsets[1:])
    total = int(offsets[-1])

    # output row validity: null inputs and non-object rows are null maps;
    # empty/whitespace-only rows are valid empty maps (reference "{}" fill)
    out_valid_np = np.asarray(in_valid) & (row_ok | np.asarray(s["empty_row"]))
    out_valid = None if out_valid_np.all() else jnp.asarray(out_valid_np)

    if total == 0:
        struct = Column.make_struct(
            key=Column.from_pylist([], dtypes.STRING),
            value=Column.from_pylist([], dtypes.STRING))
        return Column.make_list(jnp.asarray(offsets), struct,
                                validity=out_valid)

    colon_mask = np.asarray(s["colon1"]) & row_ok[:, None]
    rows_flat, cols_flat = np.nonzero(colon_mask)      # row-major order
    prow = jnp.asarray(rows_flat.astype(np.int32))
    pcol = jnp.asarray(cols_flat.astype(np.int32))

    key_col, val_col, k_quoted = _extract_pairs(
        s["chars"], s["prev_scan"], s["next_scan"], s["nnw"], s["pnw"],
        prow, pcol)
    unquoted = np.asarray(~k_quoted)
    if unquoted.any():
        bad = int(rows_flat[np.flatnonzero(unquoted)[0]])
        raise ValueError(f"JSON object key must be a quoted string "
                         f"(row {bad}): {column.to_pylist()[bad]!r}")
    struct = Column.make_struct(key=key_col, value=val_col)
    return Column.make_list(jnp.asarray(offsets), struct, validity=out_valid)


def _extract_pairs(chars, prev_scan, next_scan, nnw, pnw, prow, pcol):
    """Gather trimmed, unquoted key/value spans for each (row, colon)."""
    L = chars.shape[1]

    def span(a, b):
        """Trimmed [a, b) within row `prow`, then quote-stripped."""
        ts = jnp.take_along_axis(nnw[prow], jnp.clip(a, 0, L - 1)[:, None],
                                 axis=1)[:, 0]
        te = jnp.take_along_axis(pnw[prow], jnp.clip(b - 1, 0, L - 1)[:, None],
                                 axis=1)[:, 0] + 1
        ts = jnp.minimum(ts, b)
        te = jnp.maximum(te, a)
        empty = ts >= te
        first = jnp.take_along_axis(chars[prow],
                                    jnp.clip(ts, 0, L - 1)[:, None],
                                    axis=1)[:, 0]
        last = jnp.take_along_axis(chars[prow],
                                   jnp.clip(te - 1, 0, L - 1)[:, None],
                                   axis=1)[:, 0]
        quoted = ~empty & (first == ord('"')) & (last == ord('"')) & \
            (te - ts >= 2)
        ts = jnp.where(quoted, ts + 1, ts)
        te = jnp.where(quoted, te - 1, te)
        return ts, jnp.where(empty, ts, te), quoted

    prev_d = jnp.take_along_axis(prev_scan[prow],
                                 jnp.clip(pcol, 0, L - 1)[:, None],
                                 axis=1)[:, 0]
    next_d = jnp.take_along_axis(next_scan[prow],
                                 jnp.clip(pcol + 1, 0, L - 1)[:, None],
                                 axis=1)[:, 0]
    k_start, k_end, k_quoted = span(prev_d + 1, pcol)
    v_start, v_end, _ = span(pcol + 1, next_d)
    v_empty = v_start >= v_end

    def build(starts, ends):
        out_len = (ends - starts).astype(jnp.int32)
        max_len = int(jnp.max(out_len)) if out_len.shape[0] else 0
        Lout = _round_bucket(max(1, max_len))
        idx = starts[:, None] + jnp.arange(Lout, dtype=jnp.int32)[None, :]
        take = jnp.take_along_axis(chars[prow], jnp.clip(idx, 0, L - 1),
                                   axis=1)
        in_r = jnp.arange(Lout, dtype=jnp.int32)[None, :] < out_len[:, None]
        padded_out = jnp.where(in_r, take, jnp.uint8(0))
        offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(out_len)]).astype(jnp.int32)
        total = int(offs[-1])
        dest = offs[:-1, None] + jnp.arange(Lout, dtype=jnp.int32)[None, :]
        dest = jnp.where(in_r, dest, total)
        flat = jnp.zeros((total + 1,), jnp.uint8).at[dest.reshape(-1)].set(
            padded_out.reshape(-1), mode="drop")[:total]
        return make_string_column(flat, offs)

    return build(k_start, k_end), build(v_start, v_end), k_quoted
