"""Spark-facing logical type system for the TPU-native columnar engine.

Mirrors the type surface the reference exposes through cudf's type system
(`ai.rapids.cudf.DType` used by e.g. /root/reference/src/main/java/com/nvidia/spark/
rapids/jni/CastStrings.java:49-66 and decimal precision selection in
/root/reference/src/main/cpp/src/cast_string.cu:818-827), re-designed for an
XLA/JAX substrate:

- fixed-width types map 1:1 onto dense jnp arrays;
- DECIMAL32/64 are a physical int32/int64 plus a (precision, scale) tag;
- DECIMAL128 is four little-endian uint32 limbs per row (TPU has no native
  int128; arithmetic is limb math — see ops/decimal_utils.py);
- STRING is (chars uint8, offsets int32, validity) — Arrow layout;
- TIMESTAMP is int64 microseconds since epoch (Spark's TimestampType),
  DATE is int32 days since epoch (Spark's DateType).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import jax.numpy as jnp


class Kind(enum.Enum):
    BOOL = "bool"
    INT8 = "int8"
    UINT8 = "uint8"            # binary payloads (LIST<UINT8> rows)
    INT16 = "int16"
    INT32 = "int32"
    INT64 = "int64"
    UINT64 = "uint64"          # Spark conv() works in the unsigned-64 domain
    FLOAT32 = "float32"
    FLOAT64 = "float64"
    DECIMAL32 = "decimal32"
    DECIMAL64 = "decimal64"
    DECIMAL128 = "decimal128"
    STRING = "string"
    DATE32 = "date32"          # days since 1970-01-01 (Spark DateType)
    TIMESTAMP_US = "timestamp" # microseconds since epoch (Spark TimestampType)
    TIMESTAMP_S = "timestamp_s"    # seconds since epoch
    TIMESTAMP_MS = "timestamp_ms"  # milliseconds since epoch
    LIST = "list"
    STRUCT = "struct"


# Spark's precision boundaries for picking decimal storage width
# (reference: cast_string.cu:818-827 picks DECIMAL32 for precision<=9,
# DECIMAL64 for <=18, DECIMAL128 for <=38).
MAX_DEC32_PRECISION = 9
MAX_DEC64_PRECISION = 18
MAX_DEC128_PRECISION = 38


@dataclasses.dataclass(frozen=True)
class DType:
    kind: Kind
    precision: Optional[int] = None   # decimals only
    scale: Optional[int] = None       # decimals only; Spark convention: scale >= 0
    children: tuple = ()              # LIST: (element,), STRUCT: (fields...)
    field_names: tuple = ()           # STRUCT only

    # ---- convenience predicates -------------------------------------------------
    @property
    def is_decimal(self) -> bool:
        return self.kind in (Kind.DECIMAL32, Kind.DECIMAL64, Kind.DECIMAL128)

    @property
    def is_integer(self) -> bool:
        # UINT64 is deliberately excluded: it exists only for conv()'s
        # unsigned-64 domain (CastStrings.toIntegersWithBase), not as a
        # general numeric type — aggregations over it would wrap at 2^63
        return self.kind in (Kind.INT8, Kind.INT16, Kind.INT32, Kind.INT64)

    @property
    def is_floating(self) -> bool:
        return self.kind in (Kind.FLOAT32, Kind.FLOAT64)

    @property
    def is_nested(self) -> bool:
        return self.kind in (Kind.LIST, Kind.STRUCT)

    @property
    def is_string(self) -> bool:
        return self.kind == Kind.STRING

    def storage_dtype(self):
        """Physical jnp dtype of the primary data buffer."""
        return {
            Kind.BOOL: jnp.bool_,
            Kind.INT8: jnp.int8,
            Kind.UINT8: jnp.uint8,
            Kind.INT16: jnp.int16,
            Kind.INT32: jnp.int32,
            Kind.INT64: jnp.int64,
            Kind.UINT64: jnp.uint64,
            Kind.FLOAT32: jnp.float32,
            Kind.FLOAT64: jnp.float64,
            Kind.DECIMAL32: jnp.int32,
            Kind.DECIMAL64: jnp.int64,
            Kind.DECIMAL128: jnp.uint32,   # (n, 4) little-endian limbs
            Kind.STRING: jnp.uint8,        # chars buffer
            Kind.DATE32: jnp.int32,
            Kind.TIMESTAMP_US: jnp.int64,
            Kind.TIMESTAMP_S: jnp.int64,
            Kind.TIMESTAMP_MS: jnp.int64,
        }[self.kind]

    def itemsize(self) -> int:
        """Bytes per row of the primary buffer (Spark row-format width)."""
        return {
            Kind.BOOL: 1, Kind.INT8: 1, Kind.UINT8: 1, Kind.INT16: 2, Kind.INT32: 4,
            Kind.INT64: 8, Kind.UINT64: 8, Kind.FLOAT32: 4, Kind.FLOAT64: 8,
            Kind.DECIMAL32: 4, Kind.DECIMAL64: 8, Kind.DECIMAL128: 16,
            Kind.DATE32: 4, Kind.TIMESTAMP_US: 8,
            Kind.TIMESTAMP_S: 8, Kind.TIMESTAMP_MS: 8,
        }[self.kind]

    def __repr__(self):
        if self.is_decimal:
            return f"{self.kind.value}({self.precision},{self.scale})"
        if self.kind == Kind.LIST:
            return f"list<{self.children[0]!r}>"
        if self.kind == Kind.STRUCT:
            inner = ", ".join(f"{n}: {c!r}" for n, c in zip(self.field_names, self.children))
            return f"struct<{inner}>"
        return self.kind.value


# Singletons for the common scalar types.
BOOL = DType(Kind.BOOL)
INT8 = DType(Kind.INT8)
UINT8 = DType(Kind.UINT8)
INT16 = DType(Kind.INT16)
INT32 = DType(Kind.INT32)
INT64 = DType(Kind.INT64)
UINT64 = DType(Kind.UINT64)
FLOAT32 = DType(Kind.FLOAT32)
FLOAT64 = DType(Kind.FLOAT64)
STRING = DType(Kind.STRING)
DATE32 = DType(Kind.DATE32)
TIMESTAMP_US = DType(Kind.TIMESTAMP_US)
TIMESTAMP_S = DType(Kind.TIMESTAMP_S)
TIMESTAMP_MS = DType(Kind.TIMESTAMP_MS)


def decimal(precision: int, scale: int) -> DType:
    """Pick decimal storage by precision exactly as the reference does
    (cast_string.cu:818-827)."""
    if precision <= 0 or precision > MAX_DEC128_PRECISION:
        raise ValueError(f"invalid decimal precision {precision}")
    if precision <= MAX_DEC32_PRECISION:
        kind = Kind.DECIMAL32
    elif precision <= MAX_DEC64_PRECISION:
        kind = Kind.DECIMAL64
    else:
        kind = Kind.DECIMAL128
    return DType(kind, precision=precision, scale=scale)


def list_(element: DType) -> DType:
    return DType(Kind.LIST, children=(element,))


def struct(**fields: DType) -> DType:
    return DType(Kind.STRUCT, children=tuple(fields.values()),
                 field_names=tuple(fields.keys()))
