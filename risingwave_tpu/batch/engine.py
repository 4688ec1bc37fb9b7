"""Local-mode batch engine: SELECT over MV snapshots.

Reference: the batch executor chain (src/batch/src/executor/: RowSeqScan
-> filter -> project -> agg -> order/limit) in local execution mode
(scheduler/local.rs:60). The scan source is a MaterializeExecutor
snapshot (the queryable MV) or a recovered storage table; filtering and
projection run through the same expression framework as streaming.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from risingwave_tpu.array.chunk import DataChunk
from risingwave_tpu.array.lattice import DELTA_SMALL, pow2_at_least
from risingwave_tpu.executors.materialize import MaterializeExecutor
from risingwave_tpu.sql import parser as P
from risingwave_tpu.sql.planner import (
    AGG_FUNCS,
    EXTENDED_AGGS,
    Binder,
    compile_scalar,
)


# aggregates the batch engine evaluates beyond the planner's kinds:
# DISTINCT counts (pandas nunique), string_agg / array_agg (the
# reference's ordered-set aggregates, impl/src/aggregate/string_agg.rs)
DISTINCT_AGG_NAMES = ("approx_count_distinct",)
COLLECT_AGGS = ("string_agg", "array_agg")


def _scan_cap(n: int) -> int:
    """Lanes of the chunk a scan of ``n`` rows is evaluated in: a power
    of two, never under the delta lattice's small size, so that a view
    that grows from 20 rows to 80 inside a run (a dashboard's probe of
    it, every 50 ms) does not meet a new program at 32 and at 64."""
    return pow2_at_least(max(n, DELTA_SMALL))


def _is_batch_agg(fc) -> bool:
    return isinstance(fc, P.FuncCall) and (
        fc.name in AGG_FUNCS
        or fc.name in EXTENDED_AGGS
        or fc.name in DISTINCT_AGG_NAMES
        or fc.name in COLLECT_AGGS
    )


def _and_join(conjuncts):
    out = None
    for c in conjuncts:
        out = c if out is None else P.BinaryOp("and", out, c)
    return out


def _strip_quals(ast, cols: set):
    """Rewrite qualified idents (a.x) to bare names for evaluation
    over a joined frame whose columns are disjoint across sides."""
    if isinstance(ast, P.Ident):
        if ast.name not in cols:
            raise KeyError(f"cannot resolve join column {ast}")
        return P.Ident(ast.name)
    if isinstance(ast, P.BinaryOp):
        return P.BinaryOp(
            ast.op, _strip_quals(ast.left, cols), _strip_quals(ast.right, cols)
        )
    if isinstance(ast, P.UnaryOp):
        return P.UnaryOp(ast.op, _strip_quals(ast.operand, cols))
    if isinstance(ast, P.FuncCall):
        return P.FuncCall(
            ast.name,
            tuple(
                _strip_quals(a, cols)
                if isinstance(a, (P.Ident, P.BinaryOp, P.UnaryOp, P.FuncCall))
                else a
                for a in ast.args
            ),
        )
    return ast  # literals etc. pass through


class BatchQueryEngine:
    """``tables`` maps name -> MaterializeExecutor (the MV catalog)."""

    spill_threshold_rows: "int | None" = None  # SET batch_spill_threshold
    last_spill_partitions = 0

    def __init__(self, tables: Dict[str, MaterializeExecutor]):
        self.tables = dict(tables)
        # distributed-mode task count, 0/1 = local mode; flipped like
        # the reference's QUERY_MODE session variable
        self.distributed_tasks = 0
        # session dictionary (set by SqlSession): string_agg decodes
        # VARCHAR codes, joins text, and encodes the result back
        self.strings = None
        # session catalog (set by SqlSession): array_agg decodes its
        # ELEMENTS by the arg column's logical type — the result edge
        # only decodes whole lanes, never values inside lists
        self.catalog = None

    def _elem_decoder(self, stmt, arg):
        """Per-element decode fn for collect aggregates."""
        if self.catalog is None or not isinstance(arg, P.Ident):
            return lambda v: v
        from risingwave_tpu.sql.typing import _env_of_rel
        from risingwave_tpu.types import DataType

        f = _env_of_rel(stmt.from_, self.catalog).get(arg.name)
        if f is None:
            return lambda v: v
        if f.dtype is DataType.VARCHAR and self.strings is not None:
            return lambda v: self.strings.decode_one(int(v))
        if f.dtype is DataType.DECIMAL:
            from decimal import Decimal

            sc = f.scale or 0
            return lambda v: Decimal(int(v)).scaleb(-sc)
        return lambda v: v

    def register(self, name: str, mview: MaterializeExecutor) -> None:
        self.tables[name] = mview

    def query(self, sql: str, stmt: "P.Select" = None) -> Dict[str, np.ndarray]:
        if stmt is None:
            stmt = P.parse(sql)
        if not isinstance(stmt, P.Select):
            raise ValueError("batch engine runs SELECT only")
        if self.distributed_tasks > 1:
            # distributed mode first; non-partitionable shapes fall
            # back to local (scheduler/local.rs:60 mode split)
            from risingwave_tpu.batch.distributed import (
                DistributedBatchRunner,
            )

            out = DistributedBatchRunner(
                self, self.distributed_tasks
            ).query(stmt)
            if out is not None:
                having = getattr(stmt, "having", None)
                if having is not None:
                    # merged rows are COMPLETE (two-phase agg finished):
                    # filtering here is correct for global aggregates
                    # and idempotent for grouped ones
                    out = self._having_filter(
                        having, {k: np.asarray(v) for k, v in out.items()}
                    )
                out = self._distinct(stmt, out)
                return out
        if isinstance(stmt.from_, P.Join):
            cols, alias = self._join_scan(stmt.from_), None
        elif isinstance(stmt.from_, P.TableRef):
            mv = self.tables[stmt.from_.name]
            cols, alias = mv.to_numpy(), stmt.from_.alias
        elif isinstance(stmt.from_, P.SubQuery):
            # derived table: run the inner select (its own WHERE/GROUP
            # BY/ORDER BY/LIMIT apply) and scan its result — NULL
            # companions fold into object lanes, the engine's nullable
            # column convention
            inner = self.query("", stmt=stmt.from_.select)
            cols = self._fold_null_lanes(inner)
            alias = stmt.from_.alias
        else:
            raise ValueError(
                "batch FROM must be an MV name, join, or subquery"
            )
        out = self._run_select_over(stmt, cols, alias)
        out = self._distinct(stmt, out)

        # OrderBy + Limit (src/batch/src/executor/{order_by,limit}.rs)
        out = self._order_limit(stmt, out)
        return out

    @staticmethod
    def _fold_null_lanes(out):
        """{v, v__null} pairs -> object lanes with None cells (the
        engine's nullable-column convention for scan inputs)."""
        cols = {}
        for k, v in out.items():
            if k.endswith("__null"):
                continue
            nl = out.get(k + "__null")
            arr = np.asarray(v)
            if nl is not None and np.asarray(nl).any():
                vals = arr.tolist()
                cols[k] = np.asarray(
                    [
                        None if m else x
                        for x, m in zip(vals, np.asarray(nl, bool))
                    ],
                    object,
                )
            else:
                cols[k] = arr
        return cols

    @staticmethod
    def _chunk_from_cols(cols, cap, nulls=None):
        """Snapshot columns -> DataChunk; object-dtype lanes (python-
        backend MVs embed None for SQL NULL) split into a numeric lane
        + null lane so expression eval stays NULL-strict. Callers with
        explicit null masks (e.g. agg ``__null`` companions) pass them
        via ``nulls`` and they merge with the derived ones."""
        data, nl_map = {}, {k: np.asarray(v, bool) for k, v in (nulls or {}).items()}
        for k, v in cols.items():
            a = np.asarray(v)
            if a.dtype == object:
                vals = a.tolist()
                nl = np.asarray([x is None for x in vals], bool)
                data[k] = np.asarray([0 if x is None else x for x in vals])
                if nl.any():
                    nl_map[k] = nl_map.get(k, False) | nl
            else:
                data[k] = a
        return DataChunk.from_numpy(data, cap, nulls=nl_map or None)

    def _run_select_over(self, stmt, cols, alias=None):
        """Filter -> agg/projection over one scan's columns (the task
        body shared by local mode and distributed partition tasks)."""
        n = len(next(iter(cols.values()))) if cols else 0

        # RowSeqScan -> chunk -> Filter via the shared expr framework
        schema = {k: v.dtype for k, v in cols.items()}
        binder = Binder(schema, alias)
        if n and stmt.where is not None:
            cap = _scan_cap(n)
            chunk = self._chunk_from_cols(cols, cap)
            keep_v, keep_n = compile_scalar(stmt.where, binder).eval(chunk)
            keep = np.asarray(keep_v).astype(bool)
            if keep_n is not None:
                keep &= ~np.asarray(keep_n)
            keep = keep[:n] & np.asarray(chunk.valid)[:n]
            cols = {k: v[keep] for k, v in cols.items()}
            n = int(keep.sum())

        # window functions (src/batch/src/executor/over_window.rs):
        # pandas per-partition transforms over the filtered scan
        if any(
            isinstance(it.expr, P.WindowFuncCall) for it in stmt.items
        ):
            if stmt.group_by:
                raise NotImplementedError(
                    "window functions over GROUP BY: aggregate in a "
                    "derived table first"
                )
            return self._over_window(stmt, cols, n, binder)

        # aggregation / projection
        if stmt.group_by:
            keys = [binder.resolve(g) for g in stmt.group_by]
            out = self._group_agg(stmt, cols, keys, binder)
            having = getattr(stmt, "having", None)
            if having is not None:
                out = self._having_filter(having, out)
        else:
            out = {}
            chunk_cache = [None]
            for i, item in enumerate(stmt.items):
                if _is_batch_agg(item.expr):
                    name = item.alias or f"{item.expr.name}_{i}"
                    vals, isnull = self._scalar_agg(
                        item.expr, cols, n, binder, stmt=stmt
                    )
                    out[name] = vals
                    if isnull:
                        out[name + "__null"] = np.array([True])
                else:
                    # unaliased names must match sql/typing's inference
                    # (the result edge keys decode on them)
                    if item.alias:
                        name = item.alias
                    elif isinstance(item.expr, P.Ident):
                        name = item.expr.name
                    elif isinstance(item.expr, P.FuncCall):
                        name = f"{item.expr.name}_{i}"
                    else:
                        name = f"col{i}"
                    vals, nl = self._eval_item(
                        item.expr, cols, n, binder, chunk_cache
                    )
                    out[name] = vals
                    if nl is not None and nl.any():
                        out[name + "__null"] = nl
            having = getattr(stmt, "having", None)
            if having is not None:
                # HAVING over a GLOBAL aggregate filters its single row
                out = self._having_filter(having, {
                    k: np.asarray(v) for k, v in out.items()
                })
        return out

    @staticmethod
    def _distinct(stmt, out):
        if not getattr(stmt, "distinct", False) or not out:
            return out
        import pandas as pd

        df = pd.DataFrame(out).drop_duplicates()
        return {k: df[k].to_numpy() for k in out}

    def _having_filter(self, having, out):
        """HAVING over the grouped OUTPUT columns (keys + agg aliases),
        evaluated through the shared expression framework."""
        value_cols = {
            k: v for k, v in out.items() if not k.endswith("__null")
        }
        # a NULL aggregate (min/sum over zero surviving rows) must make
        # the HAVING predicate NULL -> row dropped, not compare its
        # numeric fill value; carry the __null companions as masks
        null_masks = {
            k[: -len("__null")]: np.asarray(v, bool)
            for k, v in out.items()
            if k.endswith("__null") and k[: -len("__null")] in value_cols
        }
        n = len(next(iter(value_cols.values()))) if value_cols else 0
        if not n:
            return out
        hb = Binder(
            {k: np.asarray(v).dtype for k, v in value_cols.items()}, None
        )
        cap = _scan_cap(n)
        chunk = self._chunk_from_cols(value_cols, cap, nulls=null_masks or None)
        kv, kn = compile_scalar(having, hb).eval(chunk)
        keep = np.asarray(kv).astype(bool)[:n]
        if kn is not None:
            keep &= ~np.asarray(kn)[:n]
        return {k: np.asarray(v)[keep] for k, v in out.items()}

    def _order_limit(self, stmt, out):
        if stmt.order_by:
            lanes = []
            for ident, desc in reversed(stmt.order_by):
                if ident.name not in out:
                    raise ValueError(
                        f"ORDER BY column {ident.name!r} must appear "
                        "in the SELECT list (this engine sorts the "
                        "projected output)"
                    )
                lane = np.asarray(out[ident.name])
                nl = out.get(ident.name + "__null")
                if lane.dtype == object:
                    # None-embedded object lane (a folded subquery
                    # output): split into fill values + a null mask
                    vals = lane.tolist()
                    onl = np.asarray([x is None for x in vals], bool)
                    lane = np.asarray(
                        [0 if m else x for x, m in zip(vals, onl)]
                    )
                    nl = onl if nl is None else (np.asarray(nl, bool) | onl)
                lanes.append(-lane if desc else lane)
                if nl is not None:
                    # Postgres: NULL sorts as larger than every value —
                    # last under ASC, first under DESC; the null lane
                    # must dominate the fill value, so append it AFTER
                    # (lexsort: later keys are more significant)
                    nl = np.asarray(nl, bool)
                    lanes.append(~nl if desc else nl)
            order = np.lexsort(tuple(lanes))
            out = {k: v[order] for k, v in out.items()}
        if stmt.limit is not None:
            out = {k: v[: stmt.limit] for k, v in out.items()}
        return out

    def _over_window(self, stmt, cols, n, binder):
        """Batch OVER() (reference: src/batch/src/executor/
        over_window.rs): row_number/rank/dense_rank/lag/lead +
        sum/min/max/count over full partitions, plus trailing ROWS
        frames for the reducers. Output preserves scan row order."""
        import pandas as pd

        df = pd.DataFrame(cols)
        out: Dict[str, np.ndarray] = {}
        for i, item in enumerate(stmt.items):
            ast = item.expr
            if isinstance(ast, P.Ident):
                name = binder.resolve(ast)
                out[item.alias or name] = np.asarray(cols[name])
                continue
            if not isinstance(ast, P.WindowFuncCall):
                raise NotImplementedError(
                    "window SELECTs mix bare columns and OVER() calls "
                    "only (wrap expressions in a derived table)"
                )
            part = [binder.resolve(c) for c in ast.partition_by]
            if len(ast.order_by) > 1:
                raise NotImplementedError(
                    "OVER (... ORDER BY) supports one order column"
                )
            ocol = odesc = None
            if ast.order_by:
                oident, odesc = ast.order_by[0]
                ocol = binder.resolve(oident)
            order = df.sort_values(
                part + ([ocol] if ocol else []),
                ascending=[True] * len(part) + ([not odesc] if ocol else []),
                kind="stable",
            ) if (part or ocol) else df
            # count(*) and unpartitioned reducers work on a constant
            # lane: rows count as rows, never skipping NULL proxies
            order = order.assign(__one=1)
            # dropna=False: SQL puts NULL partition keys in their own
            # partition — pandas' default silently DROPS those rows
            gb = (
                order.groupby(part, sort=False, dropna=False)
                if part
                else None
            )
            fn, args = ast.func.name, ast.func.args
            if getattr(ast.func, "distinct", False):
                raise NotImplementedError(
                    f"{fn}(DISTINCT ...) OVER (...) unsupported"
                )
            name = item.alias or f"{fn}_{i}"
            nl = None
            if fn == "row_number":
                s = (gb.cumcount() if gb is not None else
                     pd.Series(np.arange(len(order)), index=order.index)) + 1
            elif fn in ("rank", "dense_rank"):
                if ocol is None:
                    raise ValueError(f"{fn}() needs ORDER BY")
                method = "min" if fn == "rank" else "dense"
                src = gb[ocol] if gb is not None else order[ocol]
                s = src.rank(method=method, ascending=not odesc)
            elif fn in ("lag", "lead"):
                col = binder.resolve(args[0])
                k = int(args[1].value) if len(args) > 1 else 1
                k = k if fn == "lag" else -k
                s = (gb[col].shift(k) if gb is not None
                     else order[col].shift(k))
                if len(args) > 2:
                    if not isinstance(args[2], P.Literal):
                        raise ValueError(
                            "lag/lead default must be a literal"
                        )
                    s = s.fillna(args[2].value)
                else:
                    nl = s.isna()
            elif fn in ("sum", "min", "max", "count"):
                if args == ("*",):
                    if fn != "count":
                        raise ValueError(f"{fn}(*) unsupported")
                    col = "__one"  # count ROWS, not non-NULL proxies
                    fn_eff = "sum"
                else:
                    col = binder.resolve(args[0])
                    fn_eff = fn
                if ast.frame is not None:
                    lo, hi = ast.frame
                    if hi != 0 or lo > 0:
                        raise NotImplementedError(
                            "batch ROWS frames support trailing "
                            "windows (N PRECEDING .. CURRENT ROW)"
                        )
                    window = -lo + 1
                    roll = (
                        gb[col] if gb is not None else order[col]
                    ).rolling(window, min_periods=1)
                    agg = {"count": "count"}.get(fn_eff, fn_eff)
                    s = getattr(roll, agg)()
                    if gb is not None:
                        s = s.reset_index(level=list(range(len(part))),
                                          drop=True)
                elif ocol is not None:
                    # SQL default frame with ORDER BY: RUNNING
                    # aggregate (RANGE UNBOUNDED PRECEDING .. CURRENT
                    # ROW) — computed as ROWS-cumulative, then ORDER-
                    # BY peers share the frame end (transform 'last')
                    src = gb[col] if gb is not None else order[col]
                    if fn_eff == "count":
                        s = src.transform(
                            lambda x: x.notna().cumsum()
                        ) if gb is not None else order[col].notna().cumsum()
                    else:
                        cum = {"sum": "cumsum", "min": "cummin",
                               "max": "cummax"}[fn_eff]
                        s = getattr(src, cum)()
                    peer_keys = [order[c] for c in part] + [order[ocol]]
                    s = s.groupby(peer_keys, dropna=False).transform(
                        "last"
                    )
                else:
                    s = (
                        gb[col].transform(fn_eff)
                        if gb is not None
                        else pd.Series(
                            getattr(order[col], fn_eff)(),
                            index=order.index,
                        )
                    )
            else:
                raise NotImplementedError(
                    f"window function {fn!r} unsupported in batch"
                )
            s = s.reindex(df.index).sort_index()
            vals = s.to_numpy()
            if nl is None and pd.isna(vals).any():
                nl = pd.Series(vals).isna()
            if nl is not None:
                nlv = np.asarray(nl.reindex(df.index).sort_index()
                                 if hasattr(nl, "reindex") else nl, bool)
                if nlv.any():
                    out[name + "__null"] = nlv
                    vals = np.asarray(
                        [0 if m else v for v, m in zip(vals.tolist(),
                                                       nlv.tolist())]
                    )
            if fn in (
                "row_number", "rank", "dense_rank", "count"
            ) and np.issubdtype(np.asarray(vals).dtype, np.floating):
                # pandas rank/rolling-count return float; these are
                # integral by definition
                a = np.asarray(vals, np.float64)
                vals = np.where(np.isnan(a), 0, a).astype(np.int64)
            out[name] = np.asarray(vals)
        return out

    @staticmethod
    def _join_quals(rel) -> set:
        """Every alias addressable inside a (possibly nested) join."""
        if isinstance(rel, P.Join):
            return BatchQueryEngine._join_quals(
                rel.left
            ) | BatchQueryEngine._join_quals(rel.right)
        return {rel.alias or rel.name}

    def _join_scan(self, join: P.Join) -> Dict[str, np.ndarray]:
        """Batch join over MV scans (reference: the batch
        HashJoinExecutor, src/batch/src/executor/join/), LEFT-DEEP
        multi-way: a nested left join evaluates recursively and its
        result becomes the probe side (the same tree shape the
        streaming planner lowers to). Column names must be disjoint
        across sides (alias/rename upstream); outer joins surface
        missing ints as NaN-capable float lanes."""
        import pandas as pd

        if isinstance(join.right, P.Join):
            raise ValueError(
                "batch joins are left-deep: nest on the left side"
            )

        def side(rel):
            if not isinstance(rel, P.TableRef):
                raise ValueError("batch join sides must be MV names")
            df = pd.DataFrame(self.tables[rel.name].to_numpy())
            # hidden planner lanes (_row_id) are not addressable in
            # batch SQL and would collide across sides
            df = df[[c for c in df.columns if not c.startswith("_")]]
            return rel.alias or rel.name, df

        if isinstance(join.left, P.Join):
            ldf = pd.DataFrame(self._join_scan(join.left))
            lquals = self._join_quals(join.left)
        else:
            lname, ldf = side(join.left)
            lquals = {lname}
        rname, rdf = side(join.right)
        overlap = set(ldf.columns) & set(rdf.columns)
        if overlap:
            raise ValueError(
                f"join sides share column names {overlap}; alias them apart"
            )

        pairs = []
        residual = []  # non-equi conjuncts -> NL/post-filter path

        def resolve(ident: P.Ident) -> str:
            if ident.qualifier in lquals and ident.name in ldf.columns:
                return ident.name
            if ident.qualifier == rname and ident.name in rdf.columns:
                return ident.name
            if ident.qualifier is None and (
                (ident.name in ldf.columns) != (ident.name in rdf.columns)
            ):
                return ident.name
            raise KeyError(f"cannot resolve join column {ident}")

        def walk(e):
            if isinstance(e, P.BinaryOp) and e.op == "and":
                walk(e.left)
                walk(e.right)
                return
            if (
                isinstance(e, P.BinaryOp)
                and e.op == "="
                and isinstance(e.left, P.Ident)
                and isinstance(e.right, P.Ident)
            ):
                try:
                    a, b = resolve(e.left), resolve(e.right)
                except KeyError:
                    residual.append(e)
                    return
                if a in ldf.columns and b in rdf.columns:
                    pairs.append((a, b))
                    return
                if b in ldf.columns and a in rdf.columns:
                    pairs.append((b, a))
                    return
                # same-side equality: an ordinary predicate
            residual.append(e)  # theta predicate: NL / post-filter

        walk(join.on)
        jt = join.join_type
        if not pairs:
            # NO equi keys: NESTED-LOOP join (reference: src/batch/src/
            # executor/join/nested_loop_join.rs) — cross product
            # filtered by the full ON predicate
            if jt not in ("inner", "left"):
                raise ValueError(
                    "non-equi batch joins support INNER/LEFT only"
                )
            return self._nl_join(ldf, rdf, join.on, jt)
        if residual and jt != "inner":
            raise ValueError(
                "equi + residual ON predicates support INNER joins "
                "only (outer-join padding happens before the residual)"
            )
        lk = [p[0] for p in pairs]
        rk = [p[1] for p in pairs]
        if jt in ("inner", "left", "right", "full"):
            how = {"full": "outer"}.get(jt, jt)
            m = ldf.merge(rdf, left_on=lk, right_on=rk, how=how)
        elif jt in ("left_semi", "left_anti"):
            hit = ldf.merge(
                rdf[rk].drop_duplicates(), left_on=lk, right_on=rk,
                how="left", indicator=True,
            )["_merge"] == "both"
            m = ldf[hit.values] if jt == "left_semi" else ldf[~hit.values]
        elif jt in ("right_semi", "right_anti"):
            hit = rdf.merge(
                ldf[lk].drop_duplicates(), left_on=rk, right_on=lk,
                how="left", indicator=True,
            )["_merge"] == "both"
            m = rdf[hit.values] if jt == "right_semi" else rdf[~hit.values]
        else:
            raise ValueError(f"unknown join type {jt!r}")
        out = {c: m[c].to_numpy() for c in m.columns if c != "_merge"}
        if residual:
            keep = self._eval_on(out, _and_join(residual))
            out = {k: v[keep] for k, v in out.items()}
        return out

    def _nl_join(self, ldf, rdf, on, jt):
        """Cross product + predicate filter; LEFT pads unmatched probe
        rows with NULLs (nested_loop_join.rs semantics). O(|L|*|R|) by
        nature — the optimizer should have picked equi keys if any."""
        import pandas as pd

        lx = ldf.assign(__x=1, __lid=np.arange(len(ldf)))
        rx = rdf.assign(__x=1)
        cross = lx.merge(rx, on="__x").drop(columns="__x")
        cols = {c: cross[c].to_numpy() for c in cross.columns}
        keep = (
            self._eval_on(cols, on)
            if len(cross)
            else np.zeros(0, bool)
        )
        inner = cross[keep]
        if jt == "left":
            matched = set(inner["__lid"].tolist())
            miss = lx[~lx["__lid"].isin(matched)].drop(columns="__x")
            pad = pd.DataFrame(
                {c: [None] * len(miss) for c in rdf.columns}
            )
            pad.index = miss.index
            inner = pd.concat(
                [inner, pd.concat([miss, pad], axis=1)],
                ignore_index=True,
            )
        return {
            c: inner[c].to_numpy()
            for c in inner.columns
            if c != "__lid"
        }

    def _eval_on(self, cols, on) -> np.ndarray:
        """Evaluate an ON predicate over joined columns: qualifiers
        strip to bare names (sides are disjoint by construction);
        NULL comparisons drop the row (SQL join semantics)."""
        n = len(next(iter(cols.values()))) if cols else 0
        if n == 0:
            return np.zeros(0, bool)
        stripped = _strip_quals(on, set(cols))
        cap = _scan_cap(n)
        # float NaN is this engine's outer-join NULL encoding: a NaN
        # cell must make the predicate NULL (drop), not compare as a
        # value (NaN != x is True in IEEE, NULL != x is NULL in SQL)
        nan_nulls = {}
        for k, v in cols.items():
            a = np.asarray(v)
            if np.issubdtype(a.dtype, np.floating) and np.isnan(a).any():
                nan_nulls[k] = np.isnan(a)
        chunk = self._chunk_from_cols(cols, cap, nulls=nan_nulls or None)
        binder = Binder(
            {k: np.asarray(v).dtype for k, v in cols.items()}, None
        )
        kv, kn = compile_scalar(stripped, binder).eval(chunk)
        keep = np.asarray(kv).astype(bool)[:n]
        if kn is not None:
            keep &= ~np.asarray(kn)[:n]
        return keep

    def _eval_item(self, ast, cols, n, binder, chunk_cache=None):
        """-> (values, null_lane | None): computed items keep their SQL
        NULLs (a UDF error row, NULL-strict arithmetic). ``chunk_cache``
        (a one-slot list) shares the converted DataChunk across a
        select's items — the object-lane None-scan is O(rows*cols)."""
        if isinstance(ast, P.Ident):
            return cols[binder.resolve(ast)], None
        cap = _scan_cap(n) if n else 1
        if chunk_cache is not None and chunk_cache[0] is not None:
            chunk = chunk_cache[0]
        else:
            chunk = self._chunk_from_cols(cols, cap)
            if chunk_cache is not None:
                chunk_cache[0] = chunk
        v, nl = compile_scalar(ast, binder).eval(chunk)
        return np.asarray(v)[:n], (
            np.asarray(nl)[:n] if nl is not None else None
        )

    def _scalar_agg(self, fc, cols, n, binder, stmt=None):
        """NULL-aware global aggregate: NULL cells (None in object
        lanes) are skipped; sum/min/max over zero surviving rows is SQL
        NULL — returned as (values, is_null) so the caller emits the
        ``__null`` companion; count(*) / count(col) never is."""
        if fc.args == ("*",):
            if fc.name != "count":
                raise ValueError(f"{fc.name}(*) unsupported")
            return np.array([n]), False
        x = np.asarray(cols[binder.resolve(fc.args[0])])
        if x.dtype == object:
            live = np.asarray([v for v in x.tolist() if v is not None])
        elif np.issubdtype(x.dtype, np.floating):
            live = x[~np.isnan(x)]  # outer joins surface NULL as NaN
        else:
            live = x
        if fc.name in DISTINCT_AGG_NAMES or getattr(fc, "distinct", False):
            if fc.name not in ("count",) + DISTINCT_AGG_NAMES:
                raise NotImplementedError(
                    f"{fc.name}(DISTINCT ...) unsupported"
                )
            return np.array([len(set(live.tolist()))]), False
        if fc.name in COLLECT_AGGS:
            if fc.name == "array_agg":
                if len(x) == 0:
                    return np.array([0]), True  # zero rows -> NULL
                # PG array_agg PRESERVES NULL elements
                edec = (
                    self._elem_decoder(stmt, fc.args[0])
                    if stmt is not None
                    else (lambda v: v)
                )
                arr = np.empty(1, object)
                arr[0] = [
                    None
                    if v is None or (isinstance(v, float) and np.isnan(v))
                    else edec(v)
                    for v in x.tolist()
                ]
                return arr, False
            if self.strings is None:
                raise ValueError("string_agg needs the session dictionary")
            if len(fc.args) < 2 or not isinstance(fc.args[1], P.Literal):
                raise ValueError(
                    "string_agg(col, 'sep') needs a literal separator"
                )
            if len(live) == 0:
                return np.array([0]), True  # all-NULL/empty -> NULL
            sep = str(fc.args[1].value)
            code = self.strings.encode_one(
                sep.join(self.strings.decode_one(int(c)) for c in live)
            )
            return np.array([code]), False
        if fc.name == "count":
            return np.array([len(live)]), False
        if len(live) == 0:
            return np.array([0]), True
        if fc.name in EXTENDED_AGGS:
            if fc.name in ("bool_and", "bool_or"):
                b = live.astype(bool)
                return np.array([b.all() if fc.name == "bool_and" else b.any()]), False
            f = live.astype(np.float64)
            if fc.name == "avg":
                return np.array([f.mean()]), False
            ddof = 0 if fc.name.endswith("_pop") else 1
            if len(f) <= ddof:
                return np.array([0.0]), True  # var_samp of 1 row = NULL
            var = f.var(ddof=ddof)
            if fc.name.startswith("stddev"):
                return np.array([np.sqrt(var)]), False
            return np.array([var]), False
        fn = {"sum": np.sum, "min": np.min, "max": np.max}[fc.name]
        return np.array([fn(live)]), False

    def _group_agg(self, stmt, cols, keys, binder):
        n = len(next(iter(cols.values()))) if cols else 0
        if (
            self.spill_threshold_rows is not None
            and n > self.spill_threshold_rows
        ):
            return self._group_agg_spilled(stmt, cols, keys, binder)
        return self._group_agg_mem(stmt, cols, keys, binder)

    def _group_agg_spilled(self, stmt, cols, keys, binder):
        """Spill-to-disk aggregation (reference: src/batch/src/spill/):
        hash-partition the input rows by group key into on-disk runs,
        aggregate one partition at a time (memory bounded by the
        largest partition, not the input), and concatenate — each key
        lives in exactly one partition, so results are exact."""
        import shutil
        import tempfile

        import pandas as pd

        P_PARTS = 8
        key_cols = list(keys)  # already resolved column names
        # vectorized partition hash — this branch exists FOR large n
        part = (
            pd.util.hash_pandas_object(
                pd.DataFrame({c: cols[c] for c in key_cols}), index=False
            ).to_numpy()
            % P_PARTS
        )
        # native numeric lanes save/load as-is (dtype-stable results);
        # only genuinely object lanes (None cells) stay boxed
        obj_cols = {k: np.asarray(v) for k, v in cols.items()}
        tmpdir = tempfile.mkdtemp(prefix="rw_batch_spill_")
        self.last_spill_partitions = 0
        try:
            paths = []
            for p in range(P_PARTS):
                m = part == p
                if not m.any():
                    continue
                path = f"{tmpdir}/part{p}.npz"
                np.savez(path, **{k: v[m] for k, v in obj_cols.items()})
                paths.append(path)
            self.last_spill_partitions = len(paths)
            outs = []
            for path in paths:
                z = np.load(path, allow_pickle=True)
                pcols = {k: z[k] for k in z.files}
                outs.append(
                    self._group_agg_mem(stmt, pcols, keys, binder)
                )
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
        # concatenate partition results; a __null companion present in
        # ANY partition must exist for all (False-filled elsewhere)
        names = {nm for o in outs for nm in o}
        merged: Dict[str, np.ndarray] = {}
        for nm in names:
            parts = []
            for o in outs:
                if nm in o:
                    parts.append(np.asarray(o[nm]))
                elif nm.endswith("__null"):
                    base = nm[: -len("__null")]
                    parts.append(
                        np.zeros(len(o[base]), bool)
                    )
            merged[nm] = np.concatenate(parts)
        return merged

    def _group_agg_mem(self, stmt, cols, keys, binder):
        import pandas as pd

        df = pd.DataFrame(cols)
        # coerced-numeric companions for extended aggregates (object
        # lanes carry None cells; to_numeric makes them NaN, which every
        # pandas reducer skips — PG NULL-skipping semantics)
        for item in stmt.items:
            fc = item.expr
            if (
                isinstance(fc, P.FuncCall)
                and fc.name in EXTENDED_AGGS
                and fc.args != ("*",)
            ):
                col = binder.resolve(fc.args[0])
                if f"__num_{col}" not in df:
                    df[f"__num_{col}"] = pd.to_numeric(
                        df[col], errors="coerce"
                    )
        # dropna=False: SQL groups NULL keys (the _over_window path
        # passes the same flag for the same reason)
        gb = df.groupby(keys, sort=False, dropna=False)
        out: Dict[str, np.ndarray] = {}
        frames = {}
        src_cols: Dict[str, str] = {}
        ext_kinds: Dict[str, str] = {}
        for i, item in enumerate(stmt.items):
            if isinstance(item.expr, P.Ident):
                name = binder.resolve(item.expr)
                if name not in keys:
                    raise ValueError(f"{name!r} not in GROUP BY")
                continue
            fc = item.expr
            if not _is_batch_agg(fc):
                raise ValueError("items must be keys or aggregates")
            name = item.alias or f"{fc.name}_{i}"
            if fc.args == ("*",):
                if fc.name != "count":
                    raise ValueError(f"{fc.name}(*) unsupported")
                frames[name] = gb.size()
            elif fc.name in DISTINCT_AGG_NAMES or getattr(
                fc, "distinct", False
            ):
                if fc.name not in ("count",) + DISTINCT_AGG_NAMES:
                    raise NotImplementedError(
                        f"{fc.name}(DISTINCT ...) unsupported"
                    )
                col = binder.resolve(fc.args[0])
                frames[name] = gb[col].nunique()  # NULLs excluded
            elif fc.name in COLLECT_AGGS:
                col = binder.resolve(fc.args[0])
                if fc.name == "array_agg":
                    # PG array_agg PRESERVES NULL elements; VARCHAR/
                    # DECIMAL elements decode to SQL values (the edge
                    # never decodes inside lists)
                    import pandas as pd

                    edec = self._elem_decoder(stmt, fc.args[0])
                    frames[name] = gb[col].agg(
                        lambda x: [
                            None if pd.isna(v) else edec(v) for v in x
                        ]
                    )
                else:  # string_agg(col, sep); all-NULL group -> NULL
                    if self.strings is None:
                        raise ValueError(
                            "string_agg needs the session dictionary"
                        )
                    if len(fc.args) < 2 or not isinstance(
                        fc.args[1], P.Literal
                    ):
                        raise ValueError(
                            "string_agg(col, 'sep') needs a literal "
                            "separator"
                        )
                    sep = str(fc.args[1].value)
                    dec = self.strings.decode_one
                    enc = self.strings.encode_one

                    def _sagg(x, _sep=sep, _dec=dec, _enc=enc):
                        d = x.dropna()
                        if not len(d):
                            return np.nan
                        return _enc(_sep.join(_dec(int(c)) for c in d))

                    frames[name] = gb[col].agg(_sagg)
            elif fc.name in EXTENDED_AGGS:
                col = f"__num_{binder.resolve(fc.args[0])}"
                ext_kinds[name] = fc.name
                if fc.name == "avg":
                    frames[name] = gb[col].mean()
                elif fc.name == "bool_and":
                    frames[name] = gb[col].min()  # finished to bool below
                elif fc.name == "bool_or":
                    frames[name] = gb[col].max()
                else:  # var/stddev: NaN when n <= ddof (samp of 1 row)
                    ddof = 0 if fc.name.endswith("_pop") else 1
                    v = gb[col].var(ddof=ddof)
                    frames[name] = (
                        np.sqrt(v) if fc.name.startswith("stddev") else v
                    )
            elif fc.name == "sum":
                # min_count=1: sum over an all-NULL group is SQL NULL
                # (pandas' default min_count=0 would fabricate a 0)
                col = binder.resolve(fc.args[0])
                src_cols[name] = col
                frames[name] = gb[col].sum(min_count=1)
            else:
                col = binder.resolve(fc.args[0])
                src_cols[name] = col
                frames[name] = getattr(gb[col], {
                    "count": "count", "min": "min", "max": "max"
                }[fc.name])()
        if frames:
            res = pd.DataFrame(frames).reset_index()
        else:  # batch DISTINCT: GROUP BY with no aggregates
            res = df[keys].drop_duplicates()
        for item in stmt.items:
            if isinstance(item.expr, P.Ident):
                nm = binder.resolve(item.expr)
                import pandas as pd

                lane = res[nm]
                knl = pd.isna(lane).to_numpy()
                if knl.any():
                    # the NULL group's key surfaces as SQL NULL
                    out[item.alias or nm] = np.asarray(
                        [
                            0 if m else x
                            for x, m in zip(lane.tolist(), knl.tolist())
                        ]
                    )
                    out[(item.alias or nm) + "__null"] = knl
                else:
                    out[item.alias or nm] = lane.to_numpy()
        for name in frames:
            lane = res[name]
            nl = lane.isna().to_numpy()
            if nl.any():
                # NULL agg outputs (all-NULL group): numeric fill + the
                # __null companion the result edge / HAVING understand
                vals = lane.to_numpy()
                arr = np.asarray(
                    [0 if m else x for x, m in zip(vals.tolist(), nl.tolist())]
                )
                # pandas widens int sums to float64 once any group is
                # NaN — restore the integer domain unless the SOURCE
                # column is genuinely floating
                src = df[src_cols[name]] if name in src_cols else None
                int_like = src is not None and (
                    src.dtype == object
                    and all(
                        isinstance(v, (int, np.integer))
                        for v in src.dropna().tolist()
                    )
                    or np.issubdtype(src.dtype, np.integer)
                )
                if int_like and np.issubdtype(arr.dtype, np.floating):
                    arr = arr.astype(np.int64)
                out[name] = arr
                out[name + "__null"] = nl
            else:
                out[name] = lane.to_numpy()
        # finish bool aggregates: min/max over the 0/1 numeric lane
        for name, kind in ext_kinds.items():
            if kind in ("bool_and", "bool_or"):
                out[name] = (
                    np.asarray(out[name], dtype=np.float64) != 0
                )
        return out
