"""Expression trees: the Velox TypedExpr -> CudfExpression translation layer.

The paper translates Velox expression trees into cuDF AST expressions so a
whole projection/filter evaluates as one fused kernel (cudf::compute_column),
falling back to standalone per-op kernels when the AST lacks an operation.

In JAX the analogue is direct: an expression tree evaluates to a single
traced jnp computation, and XLA fuses it into one kernel. ``Expr.evaluate``
is the fused path; string predicates over fixed-width byte matrices are the
"standalone function" fallbacks (they lower to their own dot/reduce ops).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes as dt
from .table import DeviceTable


class Expr:
    """Base class. Build with col()/lit() and python operators."""

    # -- operator sugar -----------------------------------------------------
    def _bin(self, op, other) -> "Expr":
        return BinaryOp(op, self, _wrap(other))

    def __add__(self, o): return self._bin("add", o)
    def __radd__(self, o): return BinaryOp("add", _wrap(o), self)
    def __sub__(self, o): return self._bin("sub", o)
    def __rsub__(self, o): return BinaryOp("sub", _wrap(o), self)
    def __mul__(self, o): return self._bin("mul", o)
    def __rmul__(self, o): return BinaryOp("mul", _wrap(o), self)
    def __truediv__(self, o): return self._bin("div", o)
    def __eq__(self, o): return self._bin("eq", o)          # type: ignore
    def __ne__(self, o): return self._bin("ne", o)          # type: ignore
    def __lt__(self, o): return self._bin("lt", o)
    def __le__(self, o): return self._bin("le", o)
    def __gt__(self, o): return self._bin("gt", o)
    def __ge__(self, o): return self._bin("ge", o)
    def __and__(self, o): return self._bin("and", o)
    def __or__(self, o): return self._bin("or", o)
    def __invert__(self): return UnaryOp("not", self)
    def __neg__(self): return UnaryOp("neg", self)
    def __hash__(self):  # __eq__ overload breaks default hash
        return id(self)

    def isin(self, values: Sequence[Any]) -> "Expr":
        """SQL ``IN``: true where the value equals any of ``values``."""
        return IsIn(self, tuple(values))

    def between(self, lo, hi) -> "Expr":
        """SQL ``BETWEEN``: inclusive range predicate."""
        return (self >= lo) & (self <= hi)

    def contains(self, *parts: str) -> "Expr":
        """LIKE '%a%b%' over a bytes column (ordered substring match)."""
        return BytesMatch(self, tuple(parts), "contains")

    def startswith(self, prefix: str) -> "Expr":
        """LIKE 'prefix%' over a bytes column."""
        return BytesMatch(self, (prefix,), "startswith")

    def endswith(self, suffix: str) -> "Expr":
        """LIKE '%suffix' over a (space-padded) bytes column."""
        return BytesMatch(self, (suffix,), "endswith")

    # -- evaluation ----------------------------------------------------------
    def evaluate(self, table: DeviceTable) -> jax.Array:
        """Value of this expression over a batch (one traced jnp array;
        XLA fuses the whole tree into a single kernel)."""
        raise NotImplementedError

    def out_dtype(self, schema) -> dt.DType:
        """Result dtype given an input ``name -> DType`` schema."""
        raise NotImplementedError

    def references(self) -> set:
        """Set of column names this expression reads."""
        raise NotImplementedError

    # True on node types whose evaluation lowers to elementwise vector ops
    # inside a Mosaic-compiled Pallas kernel (no gathers, searches or
    # array constants); see ``in_kernel``
    KERNEL_SAFE = False


def in_kernel(e: Expr) -> bool:
    """Whether ``e`` can be evaluated inside a compiled Pallas kernel body:
    every node of the tree has ``KERNEL_SAFE`` set."""
    return e.KERNEL_SAFE and all(
        in_kernel(v) for f in dataclasses.fields(e)
        if isinstance(v := getattr(e, f.name), Expr))


def _wrap(v) -> "Expr":
    return v if isinstance(v, Expr) else Literal(v)


@dataclasses.dataclass(eq=False)
class ColumnRef(Expr):
    """Reference to an input column by name (``col("l_quantity")``)."""

    KERNEL_SAFE = True

    name: str

    def evaluate(self, table):
        return table.columns[self.name]

    def out_dtype(self, schema):
        return schema[self.name]

    def references(self):
        return {self.name}

    def __repr__(self):
        return f"col({self.name})"


@dataclasses.dataclass(eq=False)
class Literal(Expr):
    """Constant scalar; dtype inferred from the python value if absent."""

    KERNEL_SAFE = True

    value: Any
    dtype: dt.DType = None  # inferred if None

    def __post_init__(self):
        if self.dtype is None:
            if isinstance(self.value, bool):
                self.dtype = dt.BOOL
            elif isinstance(self.value, (int, np.integer)):
                self.dtype = dt.INT32
            elif isinstance(self.value, float):
                self.dtype = dt.FLOAT32
            else:
                raise TypeError(f"cannot infer literal dtype for {self.value!r}")

    def evaluate(self, table):
        return jnp.asarray(self.value, dtype=self.dtype.jnp_dtype())

    def out_dtype(self, schema):
        return self.dtype

    def references(self):
        return set()

    def __repr__(self):
        return f"lit({self.value})"


_CMP = {"eq": jnp.equal, "ne": jnp.not_equal, "lt": jnp.less,
        "le": jnp.less_equal, "gt": jnp.greater, "ge": jnp.greater_equal}
_ARITH = {"add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
          "div": jnp.divide}
_BOOLOP = {"and": jnp.logical_and, "or": jnp.logical_or}


@dataclasses.dataclass(eq=False)
class BinaryOp(Expr):
    """Arithmetic/comparison/boolean operator over two subexpressions."""

    KERNEL_SAFE = True

    op: str
    lhs: Expr
    rhs: Expr

    def evaluate(self, table):
        a = self.lhs.evaluate(table)
        b = self.rhs.evaluate(table)
        if self.op in _CMP:
            return _CMP[self.op](a, b)
        if self.op in _BOOLOP:
            return _BOOLOP[self.op](a, b)
        fn = _ARITH[self.op]
        if self.op == "div":
            a = a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.integer) else a
        return fn(a, b)

    def out_dtype(self, schema):
        if self.op in _CMP or self.op in _BOOLOP:
            return dt.BOOL
        lt_ = self.lhs.out_dtype(schema)
        rt_ = self.rhs.out_dtype(schema)
        if self.op == "div" or "float" in (lt_.name, rt_.name) \
                or lt_.name.startswith("float") or rt_.name.startswith("float"):
            return dt.FLOAT32 if "float64" not in (lt_.name, rt_.name) else dt.FLOAT64
        # wider int wins
        return lt_ if lt_.np_dtype().itemsize >= rt_.np_dtype().itemsize else rt_

    def references(self):
        return self.lhs.references() | self.rhs.references()

    def __repr__(self):
        return f"({self.lhs} {self.op} {self.rhs})"


@dataclasses.dataclass(eq=False)
class UnaryOp(Expr):
    """``not`` / ``neg`` over one subexpression."""

    KERNEL_SAFE = True

    op: str
    operand: Expr

    def evaluate(self, table):
        v = self.operand.evaluate(table)
        return jnp.logical_not(v) if self.op == "not" else jnp.negative(v)

    def out_dtype(self, schema):
        return dt.BOOL if self.op == "not" else self.operand.out_dtype(schema)

    def references(self):
        return self.operand.references()


@dataclasses.dataclass(eq=False)
class IsIn(Expr):
    """Membership against a small literal set (SQL ``IN``)."""

    KERNEL_SAFE = True

    operand: Expr
    values: Tuple[Any, ...]

    def evaluate(self, table):
        v = self.operand.evaluate(table)
        out = jnp.zeros(v.shape, dtype=bool)
        for val in self.values:
            out = out | (v == val)
        return out

    def out_dtype(self, schema):
        return dt.BOOL

    def references(self):
        return self.operand.references()


@dataclasses.dataclass(eq=False)
class BytesMatch(Expr):
    """Substring predicates over fixed-width uint8 columns.

    contains('a','b') implements SQL LIKE '%a%b%': the parts must appear in
    order, non-overlapping. Implemented with vectorized sliding-window
    equality — the "standalone kernel" fallback path of the paper's mixed
    AST translation.
    """

    operand: Expr
    parts: Tuple[str, ...]
    mode: str  # contains | startswith | endswith

    def evaluate(self, table):
        data = self.operand.evaluate(table)  # uint8[N, W]
        n, width = data.shape
        if self.mode == "startswith":
            pat = np.frombuffer(self.parts[0].encode(), dtype=np.uint8)
            return jnp.all(data[:, : len(pat)] == jnp.asarray(pat), axis=1)
        if self.mode == "endswith":
            pat = np.frombuffer(self.parts[0].encode(), dtype=np.uint8)
            # rows are space padded; match against the trimmed end per row
            lengths = _row_lengths(data)
            idx = lengths[:, None] - len(pat) + jnp.arange(len(pat))[None, :]
            ok = idx >= 0
            gathered = jnp.take_along_axis(data, jnp.clip(idx, 0, width - 1), axis=1)
            return jnp.all((gathered == jnp.asarray(pat)) & ok, axis=1)
        # ordered multi-part contains
        earliest = jnp.zeros((n,), dtype=jnp.int32)  # min start for next part
        found_all = jnp.ones((n,), dtype=bool)
        for part in self.parts:
            pat = np.frombuffer(part.encode(), dtype=np.uint8)
            hits = _find_first(data, pat, earliest)  # -1 if absent
            found_all = found_all & (hits >= 0)
            earliest = jnp.where(hits >= 0, hits + len(pat), earliest)
        return found_all

    def out_dtype(self, schema):
        return dt.BOOL

    def references(self):
        return self.operand.references()


def _row_lengths(data: jax.Array) -> jax.Array:
    """Length of each space-padded row = 1 + last non-space position."""
    non_space = data != ord(" ")
    pos = jnp.arange(data.shape[1])[None, :]
    return jnp.max(jnp.where(non_space, pos + 1, 0), axis=1)


def _find_first(data: jax.Array, pat: np.ndarray, earliest: jax.Array) -> jax.Array:
    """First index >= earliest where ``pat`` occurs in each row, else -1."""
    n, width = data.shape
    m = len(pat)
    if m > width:
        return jnp.full((n,), -1, dtype=jnp.int32)
    nwin = width - m + 1
    # windows[i, j, k] = data[i, j + k]
    idx = jnp.arange(nwin)[:, None] + jnp.arange(m)[None, :]
    windows = data[:, idx]                                   # [N, nwin, m]
    match = jnp.all(windows == jnp.asarray(pat)[None, None, :], axis=2)
    match = match & (jnp.arange(nwin)[None, :] >= earliest[:, None])
    first = jnp.argmax(match, axis=1).astype(jnp.int32)
    any_ = jnp.any(match, axis=1)
    return jnp.where(any_, first, -1)


_YEAR_STARTS = np.array(
    [(np.datetime64(f"{y}-01-01") - np.datetime64("1970-01-01"))
     .astype("timedelta64[D]").astype(np.int32) for y in range(1970, 2040)],
    dtype=np.int32)


@dataclasses.dataclass(eq=False)
class Year(Expr):
    """EXTRACT(YEAR FROM date32) via searchsorted on year-start days."""

    operand: Expr

    def evaluate(self, table):
        days = self.operand.evaluate(table)
        idx = jnp.searchsorted(jnp.asarray(_YEAR_STARTS), days, side="right") - 1
        return (idx + 1970).astype(jnp.int32)

    def out_dtype(self, schema):
        return dt.INT32

    def references(self):
        return self.operand.references()


@dataclasses.dataclass(eq=False)
class PrefixCode(Expr):
    """First ``n`` bytes of a bytes column, decoded as a base-10 integer
    (SQL: cast(substring(col, 1, n) as int); used by Q22 country codes)."""

    operand: Expr
    n: int

    def evaluate(self, table):
        data = self.operand.evaluate(table)   # uint8[N, W]
        out = jnp.zeros(data.shape[0], dtype=jnp.int32)
        for i in range(self.n):
            out = out * 10 + (data[:, i].astype(jnp.int32) - ord("0"))
        return out

    def out_dtype(self, schema):
        return dt.INT32

    def references(self):
        return self.operand.references()


def year(e: Expr) -> Year:
    """EXTRACT(YEAR) from a date32 expression."""
    return Year(e)


def prefix_code(e: Expr, n: int) -> PrefixCode:
    """Integer decode of the first ``n`` bytes of a bytes column."""
    return PrefixCode(e, n)


def col(name: str) -> ColumnRef:
    """Reference a column by name: ``col("l_quantity") * 2.0``."""
    return ColumnRef(name)


def lit(value, dtype: dt.DType = None) -> Literal:
    """Literal scalar (dtype inferred from the python type if omitted)."""
    return Literal(value, dtype)


def date_lit(iso: str) -> Literal:
    """Date literal from 'YYYY-MM-DD', as int32 days since epoch."""
    return Literal(dt.date_to_i32(iso), dt.DATE32)
