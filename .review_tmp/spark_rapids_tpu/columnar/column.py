"""HBM-resident columnar substrate (Arrow layout) for the TPU engine.

Equivalent role to cudf's `column`/`column_view` + the JNI handle surface in the
reference (/root/reference/src/main/java/.../CastStrings.java:155-165 passes
`long` view handles; ownership contract described in SURVEY.md §1). Here a
column is a JAX pytree of dense device arrays, so whole tables flow through
`jax.jit`/`shard_map` unchanged:

- fixed-width column:  data (n,) storage-dtype, validity (n,) bool or None
- string column:       chars (total,) uint8, offsets (n+1,) int32, validity
- decimal128 column:   data (n, 4) uint32 little-endian limbs, validity
- list column:         offsets (n+1,) int32, one child column, validity
- struct column:       children columns, validity

Validity is an unpacked bool vector (vectorizes on the VPU; pack/unpack to
Arrow bitmask lives in utils/bitmask.py for wire parity — the reference ORs
packed bitmasks in utilities.cu:32).

Strings on a fixed-shape-loving XLA stack: every string kernel here is the
two-pass (measure → gather) pattern the reference uses for its strings output
(parse_uri.cu:774/854), and *input* parsing uses a padded (n, max_len) uint8
matrix built with one gather (`padded_chars`), with max_len rounded to a
bucket so jit recompiles are bounded.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes
from ..dtypes import DType, Kind


def _round_bucket(n: int, minimum: int = 8) -> int:
    """Round up to a power of two so padded-string jit shapes are bounded."""
    b = minimum
    while b < n:
        b *= 2
    return b


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """One logical column. Immutable; all mutation returns new columns."""
    dtype: DType
    length: int
    data: Optional[jnp.ndarray] = None       # primary buffer (absent for struct/list)
    validity: Optional[jnp.ndarray] = None   # (n,) bool; None == all valid
    offsets: Optional[jnp.ndarray] = None    # (n+1,) int32 for string/list
    children: Tuple["Column", ...] = ()

    # ---- pytree protocol --------------------------------------------------------
    def tree_flatten(self):
        leaves = (self.data, self.validity, self.offsets, self.children)
        aux = (self.dtype, self.length)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        data, validity, offsets, children = leaves
        dtype, length = aux
        return cls(dtype=dtype, length=length, data=data, validity=validity,
                   offsets=offsets, children=children)

    # ---- basic accessors --------------------------------------------------------
    def __len__(self) -> int:
        return self.length

    @property
    def null_mask(self) -> jnp.ndarray:
        """(n,) bool, True where valid."""
        if self.validity is None:
            return jnp.ones((self.length,), dtype=jnp.bool_)
        return self.validity

    def null_count(self) -> int:
        if self.validity is None:
            return 0
        return int(self.length - jnp.sum(self.validity))

    def has_nulls(self) -> bool:
        return self.null_count() > 0

    def with_validity(self, validity: Optional[jnp.ndarray]) -> "Column":
        return dataclasses.replace(self, validity=validity)

    # ---- string helpers ---------------------------------------------------------
    def string_lengths(self) -> jnp.ndarray:
        assert self.dtype.is_string
        return (self.offsets[1:] - self.offsets[:-1]).astype(jnp.int32)

    def max_string_length(self) -> int:
        """Host-side max row length (concrete; forces a sync)."""
        assert self.dtype.is_string
        if self.length == 0:
            return 0
        return int(jnp.max(self.string_lengths()))

    def padded_chars(self, pad_to: Optional[int] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Return ((n, L) uint8 padded char matrix, (n,) int32 lengths).

        L is `pad_to` or the power-of-two bucket >= max row length. Rows are
        zero-padded. This is the canonical input form for the vectorized
        parsing kernels (the TPU-native analogue of the reference's
        thread-per-row char loops, cast_string.cu:171).
        """
        assert self.dtype.is_string
        lens = self.string_lengths()
        if pad_to is None:
            pad_to = _round_bucket(max(1, self.max_string_length()))
        elif not isinstance(lens, jax.core.Tracer):
            # a too-small pad silently truncates rows, corrupting every
            # downstream kernel - reject when we can see concrete lengths
            m = self.max_string_length()
            if m > pad_to:
                raise ValueError(
                    f"pad_to={pad_to} is smaller than the longest string ({m})")
        starts = self.offsets[:-1]
        idx = starts[:, None] + jnp.arange(pad_to, dtype=jnp.int32)[None, :]
        in_range = jnp.arange(pad_to, dtype=jnp.int32)[None, :] < lens[:, None]
        chars = self.data if self.data.shape[0] > 0 else jnp.zeros((1,), jnp.uint8)
        gathered = jnp.take(chars, jnp.clip(idx, 0, chars.shape[0] - 1), axis=0)
        return jnp.where(in_range, gathered, jnp.uint8(0)), lens

    # ---- host interop -----------------------------------------------------------
    @staticmethod
    def from_numpy(arr: np.ndarray, dtype: Optional[DType] = None,
                   validity: Optional[np.ndarray] = None) -> "Column":
        if dtype is None:
            dtype = _np_to_dtype(arr.dtype)
        data = jnp.asarray(arr, dtype=dtype.storage_dtype())
        v = None if validity is None else jnp.asarray(validity, dtype=jnp.bool_)
        return Column(dtype=dtype, length=int(arr.shape[0]), data=data, validity=v)

    @staticmethod
    def from_pylist(values: Sequence, dtype: DType) -> "Column":
        """Build a column from a Python list; None entries become nulls."""
        n = len(values)
        valid = np.array([v is not None for v in values], dtype=bool)
        has_nulls = not valid.all()
        if dtype.is_string:
            encoded = [(v.encode() if isinstance(v, str) else (v or b"")) if v is not None else b""
                       for v in values]
            offs = np.zeros(n + 1, dtype=np.int32)
            np.cumsum([len(e) for e in encoded], out=offs[1:])
            chars = np.frombuffer(b"".join(encoded), dtype=np.uint8).copy()
            return Column(
                dtype=dtype, length=n,
                data=jnp.asarray(chars),
                offsets=jnp.asarray(offs),
                validity=jnp.asarray(valid) if has_nulls else None)
        if dtype.kind == Kind.DECIMAL128:
            limbs = np.zeros((n, 4), dtype=np.uint32)
            for i, v in enumerate(values):
                if v is None:
                    continue
                iv = int(v) & ((1 << 128) - 1)
                for j in range(4):
                    limbs[i, j] = (iv >> (32 * j)) & 0xFFFFFFFF
            return Column(dtype=dtype, length=n, data=jnp.asarray(limbs),
                          validity=jnp.asarray(valid) if has_nulls else None)
        np_dt = np.dtype(dtype.storage_dtype().__name__ if not isinstance(
            dtype.storage_dtype(), np.dtype) else dtype.storage_dtype())
        filled = [0 if v is None else v for v in values]
        if dtype.kind == Kind.BOOL:
            arr = np.array([bool(v) for v in filled], dtype=np.bool_)
        else:
            arr = np.array(filled).astype(np_dt)
        return Column(dtype=dtype, length=n, data=jnp.asarray(arr),
                      validity=jnp.asarray(valid) if has_nulls else None)

    def to_pylist(self) -> List:
        """Materialize to host Python values (None for nulls). Testing aid."""
        valid = np.asarray(self.null_mask)
        if self.dtype.is_string:
            chars = np.asarray(self.data, dtype=np.uint8).tobytes()
            offs = np.asarray(self.offsets)
            out = []
            for i in range(self.length):
                if not valid[i]:
                    out.append(None)
                else:
                    out.append(chars[offs[i]:offs[i + 1]].decode("utf-8", errors="replace"))
            return out
        if self.dtype.kind == Kind.DECIMAL128:
            limbs = np.asarray(self.data, dtype=np.uint64)
            out = []
            for i in range(self.length):
                if not valid[i]:
                    out.append(None)
                else:
                    u = int(limbs[i, 0]) | (int(limbs[i, 1]) << 32) | \
                        (int(limbs[i, 2]) << 64) | (int(limbs[i, 3]) << 96)
                    if u >= (1 << 127):
                        u -= (1 << 128)
                    out.append(u)
            return out
        if self.dtype.kind == Kind.LIST:
            offs = np.asarray(self.offsets)
            child = self.children[0].to_pylist()
            return [None if not valid[i] else child[offs[i]:offs[i + 1]]
                    for i in range(self.length)]
        if self.dtype.kind == Kind.STRUCT:
            kids = [c.to_pylist() for c in self.children]
            names = self.dtype.field_names or tuple(str(i) for i in range(len(kids)))
            return [None if not valid[i] else {n: k[i] for n, k in zip(names, kids)}
                    for i in range(self.length)]
        arr = np.asarray(self.data)
        return [None if not valid[i] else arr[i].item() for i in range(self.length)]

    # ---- constructors for nested types -----------------------------------------
    @staticmethod
    def make_list(offsets: jnp.ndarray, child: "Column",
                  validity: Optional[jnp.ndarray] = None) -> "Column":
        n = int(offsets.shape[0]) - 1
        return Column(dtype=dtypes.list_(child.dtype), length=n,
                      offsets=offsets.astype(jnp.int32), children=(child,),
                      validity=validity)

    @staticmethod
    def make_struct(validity: Optional[jnp.ndarray] = None, **fields: "Column") -> "Column":
        cols = tuple(fields.values())
        n = cols[0].length
        dt = dtypes.struct(**{k: c.dtype for k, c in fields.items()})
        return Column(dtype=dt, length=n, children=cols, validity=validity)


def _np_to_dtype(np_dtype) -> DType:
    m = {
        np.dtype(np.bool_): dtypes.BOOL,
        np.dtype(np.int8): dtypes.INT8,
        np.dtype(np.int16): dtypes.INT16,
        np.dtype(np.int32): dtypes.INT32,
        np.dtype(np.int64): dtypes.INT64,
        np.dtype(np.float32): dtypes.FLOAT32,
        np.dtype(np.float64): dtypes.FLOAT64,
    }
    try:
        return m[np.dtype(np_dtype)]
    except KeyError:
        raise TypeError(f"no logical dtype for numpy {np_dtype}")


def make_string_column(chars: jnp.ndarray, offsets: jnp.ndarray,
                       validity: Optional[jnp.ndarray] = None) -> Column:
    return Column(dtype=dtypes.STRING, length=int(offsets.shape[0]) - 1,
                  data=chars.astype(jnp.uint8), offsets=offsets.astype(jnp.int32),
                  validity=validity)


def strings_from_padded(padded: jnp.ndarray, lengths: jnp.ndarray,
                        validity: Optional[jnp.ndarray] = None) -> Column:
    """Assemble a string column from an (n, L) padded char matrix + lengths.

    The gather half of the measure→gather pattern (reference two-kernel
    strings construction, parse_uri.cu:854-875): compute offsets by scan,
    then scatter each row's live chars into the dense chars buffer.
    """
    n, L = padded.shape
    lengths = lengths.astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(lengths)])
    if isinstance(offsets, jax.core.Tracer):
        # under jit the exact char total is not concrete: size the data
        # buffer by its static upper bound n*L (Arrow permits a data buffer
        # longer than offsets[-1]; every consumer indexes through offsets)
        total = n * L
    else:
        total = int(offsets[-1])  # host sync, but the buffer is exact-sized
    in_range = jnp.arange(L, dtype=jnp.int32)[None, :] < lengths[:, None]
    dest = offsets[:-1, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
    dest = jnp.where(in_range, dest, total)  # out-of-range writes dropped
    chars = jnp.zeros((total + 1,), jnp.uint8).at[dest.reshape(-1)].set(
        padded.reshape(-1).astype(jnp.uint8), mode="drop")[:total]
    return make_string_column(chars, offsets, validity)
