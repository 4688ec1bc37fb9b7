"""Type system for the TPU dataflow plane.

Reference: src/common/src/types/ (DataType / ScalarImpl, 20+ SQL types).

The device plane is deliberately narrower than the reference's SQL type
zoo: TPUs want fixed-width vector lanes, so every device column is one of
a small set of JAX dtypes. Wider SQL types are mapped at the host edge:

- INT16/INT32          -> int32
- INT64                -> int64 (real 64-bit lanes; the package enables
                          jax x64 so these never silently truncate)
- FLOAT32              -> float32
- FLOAT64              -> float64 (real f64 — SQL DOUBLE sums must not
                          drift; XLA emulates f64 on TPU, and hot agg
                          payloads may opt into f32/bf16 explicitly)
- BOOLEAN              -> bool_
- TIMESTAMP            -> int64 milliseconds since epoch (Nexmark and the
                          reference both carry ms timestamps)
- VARCHAR              -> int32 dictionary code (dictionary lives host-side,
                          see array/dictionary.py)
- DECIMAL              -> scaled int64 at the host edge

Ops on a StreamChunk follow the reference exactly
(src/common/src/array/stream_chunk.rs:45): Insert / Delete /
UpdateDelete / UpdateInsert. ``Op.sign`` maps these to +1/-1 retraction
signs used by every aggregation kernel.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np


class Op(enum.IntEnum):
    """Row-level change op (reference: stream_chunk.rs:45)."""

    INSERT = 0
    DELETE = 1
    UPDATE_DELETE = 2
    UPDATE_INSERT = 3


def op_sign(ops: jnp.ndarray) -> jnp.ndarray:
    """+1 for Insert/UpdateInsert, -1 for Delete/UpdateDelete."""
    retract = (ops == Op.DELETE) | (ops == Op.UPDATE_DELETE)
    return jnp.where(retract, jnp.int32(-1), jnp.int32(1))


def mend_update_pairs(ops: jnp.ndarray, alive: jnp.ndarray) -> jnp.ndarray:
    """Ops with torn update pairs downgraded: U- at row i pairs with U+
    at row i+1 (chunk construction invariant, stream_chunk.rs:45); where
    only one half is still ``alive`` (a filter or a join's residual
    dropped the other), that half is a plain Delete / Insert."""
    is_ud = ops == Op.UPDATE_DELETE
    is_ui = ops == Op.UPDATE_INSERT
    ops = jnp.where(
        is_ud & alive & ~(jnp.roll(alive, -1) & jnp.roll(is_ui, -1)),
        jnp.int32(Op.DELETE), ops,
    )
    return jnp.where(
        is_ui & alive & ~(jnp.roll(alive, 1) & jnp.roll(is_ud, 1)),
        jnp.int32(Op.INSERT), ops,
    )


class DataType(enum.Enum):
    """Logical column types at the SQL/host edge.

    Wider SQL types map onto fixed-width device lanes
    (src/common/src/types/ has the same split between logical DataType
    and physical array repr):
    - DECIMAL(p, s) -> scaled int64 (value * 10^s); +,-,sum,compare run
      directly on the scaled lane, exact (Field.scale carries s);
    - INTERVAL -> two lanes, ``name.months`` int32 + ``name.usecs``
      int64 (days folded into usecs; the reference keeps months apart
      for calendar arithmetic, interval months are not a fixed usec
      count);
    - JSONB -> int32 dictionary code over the canonical JSON text
      (sort_keys serialization => equality on codes IS jsonb equality);
    - STRUCT -> one device lane per leaf field, named ``parent.child``
      (columnar decomposition — idiomatic struct-of-arrays);
    - LIST -> ``name.<i>`` element lanes padded to Field.list_cap plus
      a ``name.#`` length lane (static shapes; ragged data is hostile
      to XLA).
    Composite expansion lives in array/composite.py.
    """

    INT32 = "int32"
    INT64 = "int64"
    FLOAT32 = "float32"
    FLOAT64 = "float64"
    BOOLEAN = "boolean"
    TIMESTAMP = "timestamp"  # ms since epoch, int64 on device
    VARCHAR = "varchar"  # dictionary-encoded int32 on device
    DECIMAL = "decimal"  # scaled int64 on device (Field.scale)
    INTERVAL = "interval"  # composite: months int32 + usecs int64
    JSONB = "jsonb"  # dictionary-encoded canonical JSON, int32
    STRUCT = "struct"  # composite: child lanes (Field.children)
    LIST = "list"  # composite: padded element lanes (Field.elem/cap)
    INT256 = "int256"  # composite: 4 little-endian int64 limbs

    @property
    def device_dtype(self) -> np.dtype:
        d = {
            DataType.INT32: np.dtype(np.int32),
            DataType.INT64: np.dtype(np.int64),
            DataType.FLOAT32: np.dtype(np.float32),
            DataType.FLOAT64: np.dtype(np.float64),
            DataType.BOOLEAN: np.dtype(np.bool_),
            DataType.TIMESTAMP: np.dtype(np.int64),
            DataType.VARCHAR: np.dtype(np.int32),
            DataType.DECIMAL: np.dtype(np.int64),
            DataType.JSONB: np.dtype(np.int32),
        }.get(self)
        if d is None:
            raise TypeError(
                f"{self} is composite: expand via array/composite.py"
            )
        return d

    @property
    def is_composite(self) -> bool:
        return self in (
            DataType.INTERVAL,
            DataType.STRUCT,
            DataType.LIST,
            DataType.INT256,
        )

    @property
    def null_value(self):
        """Padding value used in invalid lanes (never observed by kernels)."""
        if self is DataType.FLOAT32:
            return np.float32(0.0)
        if self is DataType.FLOAT64:
            return np.float64(0.0)
        if self is DataType.BOOLEAN:
            return np.bool_(False)
        return self.device_dtype.type(0)


@dataclass(frozen=True)
class Interval:
    """SQL INTERVAL value (reference: src/common/src/types/interval.rs
    keeps months/days/usecs; days fold into usecs here — no calendar
    DST modelling on the dataflow plane)."""

    months: int = 0
    usecs: int = 0

    @staticmethod
    def of(months=0, days=0, hours=0, minutes=0, seconds=0, usecs=0):
        return Interval(
            months=months,
            usecs=usecs
            + int(seconds * 1_000_000)
            + minutes * 60_000_000
            + hours * 3_600_000_000
            + days * 86_400_000_000,
        )

    def total_usecs(self) -> int:
        """Fixed-usec view; months use the reference's 30-day estimate
        (interval.rs comparison semantics)."""
        return self.months * 30 * 86_400_000_000 + self.usecs


@dataclass(frozen=True)
class Field:
    """A named, typed column in a schema.

    Type parameters ride on the field (the reference puts them inside
    DataType variants): ``scale`` for DECIMAL(p, s); ``children`` (a
    Schema) for STRUCT; ``elem`` + ``list_cap`` for LIST.
    """

    name: str
    dtype: DataType
    scale: "int | None" = None
    children: "Schema | None" = None
    elem: "DataType | None" = None
    list_cap: "int | None" = None

    def __post_init__(self):
        if self.dtype is DataType.DECIMAL and self.scale is None:
            object.__setattr__(self, "scale", 6)  # pg-ish default
        if self.dtype is DataType.STRUCT and self.children is None:
            raise ValueError(f"STRUCT field {self.name!r} needs children")
        if self.dtype is DataType.LIST:
            if self.elem is None:
                raise ValueError(f"LIST field {self.name!r} needs elem")
            if self.list_cap is None:
                object.__setattr__(self, "list_cap", 16)

    def __repr__(self) -> str:  # compact for schema dumps
        return f"{self.name}:{self.dtype.value}"


@dataclass(frozen=True)
class Schema:
    """Ordered list of fields (reference: src/common/src/catalog/schema.rs)."""

    fields: tuple[Field, ...]

    def __init__(self, fields):
        object.__setattr__(
            self,
            "fields",
            tuple(
                f if isinstance(f, Field) else Field(f[0], f[1]) for f in fields
            ),
        )

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(f.name for f in self.fields)

    def field(self, name: str) -> Field:
        for f in self.fields:
            if f.name == name:
                return f
        raise KeyError(name)

    def index(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def __len__(self) -> int:
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def select(self, names) -> "Schema":
        return Schema(tuple(self.field(n) for n in names))

    def concat(self, other: "Schema", prefix: str = "") -> "Schema":
        return Schema(
            self.fields
            + tuple(Field(prefix + f.name, f.dtype) for f in other.fields)
        )


def schema_from_dtypes(dtypes: dict) -> Schema:
    """Device dtypes -> logical Schema (the reverse edge mapping; used
    when registering a planned MV's output as a catalog relation for
    MV-on-MV queries)."""
    rev = {
        np.dtype(np.int32): DataType.INT32,
        np.dtype(np.int64): DataType.INT64,
        np.dtype(np.float32): DataType.FLOAT32,
        np.dtype(np.float64): DataType.FLOAT64,
        np.dtype(np.bool_): DataType.BOOLEAN,
    }
    return Schema(
        tuple(Field(n, rev[np.dtype(d)]) for n, d in dtypes.items())
    )
