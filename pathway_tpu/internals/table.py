"""`pw.Table` — the declarative table API.

New implementation of the reference Table
(reference: python/pathway/internals/table.py, 2,675 LoC — select :382,
filter :490, groupby :942, reduce :1025, join :1164, concat :1439,
update_rows/cells :1524+, with_id_from :2089, flatten, sort, ix). Tables are
lazy: each holds a :class:`TableSpec` describing the operator that produces
it; :mod:`pathway_tpu.internals.runner` lowers reachable specs onto the
engine scope at run time.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Mapping, Sequence

from pathway_tpu.engine.reducers import ReducerKind
from pathway_tpu.engine.value import Pointer, ref_scalar
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as expr_mod
from pathway_tpu.internals import schema as schema_mod
from pathway_tpu.internals.desugaring import resolve_this, substitute
from pathway_tpu.internals.expression import (
    ColumnExpression,
    ColumnReference,
    PointerExpression,
    ReducerExpression,
    wrap_expression,
)
from pathway_tpu.internals.trace import current_trace
from pathway_tpu.internals.universe import Universe, solver

_table_counter = itertools.count()


@dataclass
class TableSpec:
    """How to produce this table: operator kind + inputs + parameters."""

    kind: str
    inputs: list["Table"] = field(default_factory=list)
    params: dict[str, Any] = field(default_factory=dict)


class JoinMode:
    INNER = "inner"
    LEFT = "left"
    RIGHT = "right"
    OUTER = "outer"


class Table:
    def __init__(
        self,
        spec: TableSpec,
        column_names: Sequence[str],
        dtypes: Mapping[str, dt.DType],
        universe: Universe | None = None,
        name: str | None = None,
    ) -> None:
        self._spec = spec
        self._column_names = list(column_names)
        self._dtypes = dict(dtypes)
        self._universe = universe if universe is not None else Universe()
        self._id = next(_table_counter)
        self._name = name or f"table_{self._id}"
        self._trace = current_trace()
        from pathway_tpu.internals import errors as _errors

        self._error_log_id = _errors.current_log_id()

    # -- introspection ------------------------------------------------------

    @property
    def schema(self) -> schema_mod.SchemaMetaclass:
        return schema_mod.schema_from_dict(
            {n: self._dtypes[n] for n in self._column_names}, name=f"{self._name}_schema"
        )

    def column_names(self) -> list[str]:
        return list(self._column_names)

    def typehints(self) -> dict[str, Any]:
        return {n: self._dtypes[n].typehint for n in self._column_names}

    def keys(self) -> list[str]:
        return list(self._column_names)

    def __repr__(self) -> str:
        cols = ", ".join(f"{n}: {self._dtypes[n]!r}" for n in self._column_names)
        return f"<pw.Table {self._name}({cols})>"

    # -- column access ------------------------------------------------------

    @property
    def id(self) -> ColumnReference:
        return ColumnReference(self, "id")

    def __getattr__(self, name: str) -> ColumnReference:
        if name.startswith("_"):
            raise AttributeError(name)
        if name not in self.__dict__.get("_column_names", ()):
            raise AttributeError(
                f"table {self._name!r} has no column {name!r}; "
                f"columns: {self._column_names}"
            )
        return ColumnReference(self, name)

    def __getitem__(self, arg: Any) -> Any:
        if isinstance(arg, str):
            if arg == "id":
                return self.id
            return ColumnReference(self, arg)
        if isinstance(arg, (list, tuple)):
            return self.select(*[self[a] for a in arg])
        if isinstance(arg, ColumnReference):
            return ColumnReference(self, arg.name)
        raise TypeError(f"cannot index table with {arg!r}")

    def __iter__(self) -> Iterable[ColumnReference]:
        return iter(ColumnReference(self, n) for n in self._column_names)

    def _ref(self, name: str) -> ColumnReference:
        return ColumnReference(self, name)

    def pointer_from(
        self, *args: Any, instance: Any = None, optional: bool = False
    ) -> PointerExpression:
        resolved = [resolve_this(a, self) for a in args]
        inst = resolve_this(instance, self) if instance is not None else None
        return PointerExpression(resolved, instance=inst)

    # -- helpers ------------------------------------------------------------

    def _resolve_kwargs(
        self, args: tuple, kwargs: dict
    ) -> dict[str, ColumnExpression]:
        from pathway_tpu.internals.thisclass import ThisStar

        out: dict[str, ColumnExpression] = {}
        for arg in args:
            if isinstance(arg, str):
                out[arg] = ColumnReference(self, arg)
                continue
            if isinstance(arg, ThisStar):
                from pathway_tpu.internals.thisclass import this

                if arg._owner is not this:
                    raise ValueError(
                        f"{arg!r} cannot be used here; use *pw.this"
                    )
                # ``*pw.this``: every column of the bound table
                for n in self._column_names:
                    out[n] = ColumnReference(self, n)
                continue
            resolved = resolve_this(arg, self)
            if isinstance(resolved, ColumnReference):
                if resolved.name == "id":
                    raise ValueError("cannot select id as a positional column")
                out[resolved.name] = resolved
            else:
                raise ValueError(
                    f"positional select arguments must be column references, got {arg!r}"
                )
        for name, value in kwargs.items():
            out[name] = resolve_this(value, self)
        return out

    def _derived(
        self,
        spec: TableSpec,
        columns: Mapping[str, dt.DType],
        universe: Universe | None = None,
        name_hint: str | None = None,
    ) -> "Table":
        return Table(
            spec,
            list(columns.keys()),
            columns,
            universe=universe,
            name=name_hint,
        )

    # -- core ops -----------------------------------------------------------

    def select(self, *args: Any, **kwargs: Any) -> "Table":
        exprs = self._resolve_kwargs(args, kwargs)
        return self._derived(
            TableSpec("select", [self], {"exprs": exprs}),
            {n: e._dtype for n, e in exprs.items()},
            universe=self._universe,
        )

    def with_columns(self, *args: Any, **kwargs: Any) -> "Table":
        exprs = self._resolve_kwargs(args, kwargs)
        combined: dict[str, ColumnExpression] = {
            n: ColumnReference(self, n) for n in self._column_names
        }
        combined.update(exprs)
        return self._derived(
            TableSpec("select", [self], {"exprs": combined}),
            {n: e._dtype for n, e in combined.items()},
            universe=self._universe,
        )

    def without(self, *columns: Any) -> "Table":
        names = set()
        for col in columns:
            if isinstance(col, str):
                names.add(col)
            else:
                resolved = resolve_this(col, self)
                assert isinstance(resolved, ColumnReference)
                names.add(resolved.name)
        keep = {
            n: ColumnReference(self, n) for n in self._column_names if n not in names
        }
        return self._derived(
            TableSpec("select", [self], {"exprs": keep}),
            {n: e._dtype for n, e in keep.items()},
            universe=self._universe,
        )

    def rename(self, names_mapping: Mapping[Any, str] | None = None, **kwargs: str) -> "Table":
        def colname(ref: Any) -> str:
            if isinstance(ref, ColumnReference):
                return ref.name
            # pw.this.x sentinel (ThisColumnReference) carries _name
            this_name = getattr(ref, "_name", None)
            return this_name if this_name is not None else str(ref)

        mapping: dict[str, str] = {}
        if names_mapping:
            for old, new in names_mapping.items():
                mapping[colname(old)] = new
        # kwargs follow reference convention: new_name=old_column
        for new, old in kwargs.items():
            mapping[colname(old)] = new
        exprs = {
            mapping.get(n, n): ColumnReference(self, n) for n in self._column_names
        }
        return self._derived(
            TableSpec("select", [self], {"exprs": exprs}),
            {name: e._dtype for name, e in exprs.items()},
            universe=self._universe,
        )

    rename_columns = rename

    def rename_by_dict(self, names_mapping: Mapping[Any, str]) -> "Table":
        return self.rename(names_mapping)

    def with_prefix(self, prefix: str) -> "Table":
        return self.rename({n: prefix + n for n in self._column_names})

    def with_suffix(self, suffix: str) -> "Table":
        return self.rename({n: n + suffix for n in self._column_names})

    def filter(self, filter_expression: Any) -> "Table":
        cond = resolve_this(filter_expression, self)
        return self._derived(
            TableSpec("filter", [self], {"condition": cond}),
            {n: self._dtypes[n] for n in self._column_names},
            universe=self._universe.subset(),
        )

    def split(self, expression: Any) -> tuple["Table", "Table"]:
        cond = resolve_this(expression, self)
        pos = self.filter(cond)
        neg = self.filter(expr_mod.UnaryOpExpression("not", cond))
        return pos, neg

    def copy(self) -> "Table":
        return self.select(
            **{n: ColumnReference(self, n) for n in self._column_names}
        )

    def cast_to_types(self, **kwargs: Any) -> "Table":
        exprs: dict[str, ColumnExpression] = {}
        for n in self._column_names:
            if n in kwargs:
                exprs[n] = expr_mod.CastExpression(ColumnReference(self, n), kwargs[n])
            else:
                exprs[n] = ColumnReference(self, n)
        return self._derived(
            TableSpec("select", [self], {"exprs": exprs}),
            {n: e._dtype for n, e in exprs.items()},
            universe=self._universe,
        )

    def update_types(self, **kwargs: Any) -> "Table":
        exprs: dict[str, ColumnExpression] = {}
        for n in self._column_names:
            if n in kwargs:
                exprs[n] = expr_mod.DeclareTypeExpression(
                    ColumnReference(self, n), kwargs[n]
                )
            else:
                exprs[n] = ColumnReference(self, n)
        return self._derived(
            TableSpec("select", [self], {"exprs": exprs}),
            {n: e._dtype for n, e in exprs.items()},
            universe=self._universe,
        )

    # -- groupby / reduce ---------------------------------------------------

    def groupby(
        self,
        *args: Any,
        id: Any = None,  # noqa: A002 — mirrors reference signature
        instance: Any = None,
        **kwargs: Any,
    ) -> "GroupedTable":
        from pathway_tpu.internals.groupbys import GroupedTable

        by: list[ColumnReference] = []
        if id is not None:
            resolved = resolve_this(id, self)
            assert isinstance(resolved, ColumnReference)
            return GroupedTable(self, [resolved], set_id=True)
        for arg in args:
            resolved = resolve_this(arg, self)
            if not isinstance(resolved, ColumnReference):
                raise ValueError("groupby arguments must be column references")
            by.append(resolved)
        if instance is not None:
            inst = resolve_this(instance, self)
            assert isinstance(inst, ColumnReference)
            by.append(inst)
        return GroupedTable(self, by, instance_last=instance is not None)

    def reduce(self, *args: Any, **kwargs: Any) -> "Table":
        from pathway_tpu.internals.groupbys import GroupedTable

        return GroupedTable(self, []).reduce(*args, **kwargs)

    def deduplicate(
        self,
        *,
        value: Any,
        instance: Any = None,
        acceptor: Callable[[Any, Any], bool],
        name: str | None = None,
    ) -> "Table":
        value_ref = resolve_this(value, self)
        instance_refs: list[ColumnExpression] = []
        if instance is not None:
            instance_refs.append(resolve_this(instance, self))
        return self._derived(
            TableSpec(
                "deduplicate",
                [self],
                {"value": value_ref, "instance": instance_refs, "acceptor": acceptor,
                 "name": name},
            ),
            {n: self._dtypes[n] for n in self._column_names},
        )

    # -- joins --------------------------------------------------------------

    def join(
        self, other: "Table", *on: Any, id: Any = None, how: str = JoinMode.INNER  # noqa: A002
    ) -> "JoinResult":
        from pathway_tpu.internals.joins import JoinResult

        return JoinResult(self, other, on, how=how, id=id)

    def join_inner(self, other: "Table", *on: Any, id: Any = None) -> "JoinResult":  # noqa: A002
        return self.join(other, *on, id=id, how=JoinMode.INNER)

    def join_left(self, other: "Table", *on: Any, id: Any = None) -> "JoinResult":  # noqa: A002
        return self.join(other, *on, id=id, how=JoinMode.LEFT)

    def join_right(self, other: "Table", *on: Any, id: Any = None) -> "JoinResult":  # noqa: A002
        return self.join(other, *on, id=id, how=JoinMode.RIGHT)

    def join_outer(self, other: "Table", *on: Any, id: Any = None) -> "JoinResult":  # noqa: A002
        return self.join(other, *on, id=id, how=JoinMode.OUTER)

    # -- set ops ------------------------------------------------------------

    def concat(self, *others: "Table") -> "Table":
        tables = [self, *others]
        dtypes: dict[str, dt.DType] = {}
        for n in self._column_names:
            dtype = self._dtypes[n]
            for o in others:
                if n not in o._dtypes:
                    raise ValueError(f"column {n!r} missing in concat operand")
                dtype = dt.lca(dtype, o._dtypes[n])
            dtypes[n] = dtype
        return self._derived(
            TableSpec("concat", tables, {}),
            dtypes,
            # concat's key set IS the union of the operands': the SAT
            # solver then proves each operand ⊆ result (cross-table
            # selects against an operand keep working)
            universe=solver.get_union(*(t._universe for t in tables)),
        )

    def concat_reindex(self, *others: "Table") -> "Table":
        reindexed = [
            t.with_id_from(t.id, expr_mod.ColumnConstExpression(i))
            for i, t in enumerate([self, *others])
        ]
        return reindexed[0].concat(*reindexed[1:])

    def update_rows(self, other: "Table") -> "Table":
        if set(other._column_names) != set(self._column_names):
            raise ValueError("update_rows requires matching columns")
        dtypes = {
            n: dt.lca(self._dtypes[n], other._dtypes[n]) for n in self._column_names
        }
        return self._derived(TableSpec("update_rows", [self, other], {}), dtypes)

    def update_cells(self, other: "Table") -> "Table":
        extra = set(other._column_names) - set(self._column_names)
        if extra:
            raise ValueError(f"update_cells: unknown columns {extra}")
        dtypes = {
            n: dt.lca(self._dtypes[n], other._dtypes[n]) if n in other._dtypes else self._dtypes[n]
            for n in self._column_names
        }
        return self._derived(
            TableSpec("update_cells", [self, other], {}),
            dtypes,
            universe=self._universe,
        )

    def __lshift__(self, other: "Table") -> "Table":
        return self.update_cells(other)

    def intersect(self, *tables: "Table") -> "Table":
        return self._derived(
            TableSpec("intersect", [self, *tables], {}),
            {n: self._dtypes[n] for n in self._column_names},
            universe=solver.get_intersection(
                self._universe, *(t._universe for t in tables)
            ),
        )

    def difference(self, other: "Table") -> "Table":
        return self._derived(
            TableSpec("subtract", [self, other], {}),
            {n: self._dtypes[n] for n in self._column_names},
            universe=solver.get_difference(self._universe, other._universe),
        )

    def restrict(self, other: "Table") -> "Table":
        return self._derived(
            TableSpec("restrict", [self, other], {}),
            {n: self._dtypes[n] for n in self._column_names},
            universe=other._universe,
        )

    def promise_universes_are_equal(self, other: "Table") -> "Table":
        """Assert both tables share a key set (reference
        Table.promise_universes_are_equal)."""
        solver.register_equal(self._universe, other._universe)
        return self

    def promise_universe_is_subset_of(self, other: "Table") -> "Table":
        solver.register_subset(self._universe, other._universe)
        return self

    def promise_universe_is_equal_to(self, other: "Table") -> "Table":
        return self.promise_universes_are_equal(other)

    def with_universe_of(self, other: "Table") -> "Table":
        solver.register_equal(self._universe, other._universe)
        return self._derived(
            TableSpec("override_universe", [self, other], {}),
            {n: self._dtypes[n] for n in self._column_names},
            universe=other._universe,
        )

    def _external_index_as_of_now(
        self,
        query_table: "Table",
        index_column: ColumnExpression,
        query_column: ColumnExpression,
        index_factory: Any,
        number_of_matches: Any = 3,
    ) -> "Table":
        """As-of-now external-index lookup (reference: Table._external_index_
        _as_of_now internals/table.py:584 → use_external_index_as_of_now).

        ``self`` is the indexed data table. Returns a table keyed by query id
        with columns ``_pw_index_reply_ids`` / ``_pw_index_reply_scores``.
        ``number_of_matches`` is an int or a ColumnExpression on the query
        table (per-query limit).
        """
        index_expr = resolve_this(index_column, self)
        query_expr = resolve_this(query_column, query_table)
        limit_expr: ColumnExpression | None = None
        k = 3
        if isinstance(number_of_matches, ColumnExpression):
            limit_expr = resolve_this(number_of_matches, query_table)
            k = 16
        else:
            k = int(number_of_matches)
        return self._derived(
            TableSpec(
                "external_index",
                [self, query_table],
                {
                    "index_expr": index_expr,
                    "query_expr": query_expr,
                    "limit_expr": limit_expr,
                    "k": k,
                    "factory": index_factory,
                },
            ),
            {
                "_pw_index_reply_ids": dt.ANY,
                "_pw_index_reply_scores": dt.ANY,
            },
            universe=query_table._universe.subset(),
        )

    # -- temporal -----------------------------------------------------------

    def windowby(
        self,
        time_expr: Any,
        *,
        window: Any,
        instance: Any = None,
        behavior: Any = None,
    ) -> Any:
        from pathway_tpu.stdlib.temporal import windowby as _windowby

        return _windowby(
            self, time_expr, window=window, instance=instance, behavior=behavior
        )

    def interval_join(
        self,
        other: "Table",
        self_time: Any,
        other_time: Any,
        interval: Any,
        *on: Any,
        how: str = JoinMode.INNER,
    ) -> Any:
        from pathway_tpu.stdlib.temporal import interval_join as _ij

        return _ij(self, other, self_time, other_time, interval, *on, how=how)

    def asof_join(
        self,
        other: "Table",
        self_time: Any,
        other_time: Any,
        *on: Any,
        how: str = JoinMode.INNER,
        direction: str = "backward",
    ) -> Any:
        from pathway_tpu.stdlib.temporal import asof_join as _aj

        return _aj(
            self, other, self_time, other_time, *on, how=how, direction=direction
        )

    def asof_now_join(
        self, other: "Table", *on: Any, how: str = JoinMode.INNER
    ) -> Any:
        from pathway_tpu.stdlib.temporal import asof_now_join as _anj

        return _anj(self, other, *on, how=how)

    # -- re-keying ----------------------------------------------------------

    def with_id_from(self, *args: Any, instance: Any = None) -> "Table":
        resolved = [resolve_this(a, self) for a in args]
        inst = resolve_this(instance, self) if instance is not None else None
        pointer = PointerExpression(resolved, instance=inst)
        return self._derived(
            TableSpec("reindex", [self], {"new_id": pointer}),
            {n: self._dtypes[n] for n in self._column_names},
        )

    def with_id(self, new_id: Any) -> "Table":
        pointer = resolve_this(new_id, self)
        return self._derived(
            TableSpec("reindex", [self], {"new_id": pointer}),
            {n: self._dtypes[n] for n in self._column_names},
        )

    # -- pointer lookup -----------------------------------------------------

    def ix(
        self,
        expression: Any,
        *,
        optional: bool = False,
        context: Any = None,
        allow_misses: bool = False,
    ) -> "Table":
        """``optional`` lets a key be ``None``; ``allow_misses`` lets it
        name a row this table does not hold (reference Table.ix): either
        gives a row of ``None``. A key that is missed and not allowed to be
        is an error of its row."""
        expression = wrap_expression(expression)
        if context is not None:
            keys_table = context
        else:
            deps = list(expression._dependencies())
            if not deps:
                raise ValueError(
                    "ix expression must reference a column (or pass "
                    "context=)"
                )
            keys_table = deps[0].table
        keys = keys_table.select(_pw_ix_key=expression)
        return self._derived(
            TableSpec(
                "ix",
                [keys, self],
                {"optional": optional, "allow_misses": allow_misses},
            ),
            {n: self._dtypes[n] for n in self._column_names},
            universe=keys_table._universe,
        )

    def ix_ref(
        self,
        *args: Any,
        optional: bool = False,
        instance: Any = None,
        context: "Table | None" = None,
        allow_misses: bool = False,
    ) -> "Table":
        """Reindex this table by primary-key expressions: desugars to
        ``self.ix(keys_table.pointer_from(*args))``, inferring the keys
        table from the expressions' column references (reference
        Table.ix_ref, python/pathway/internals/table.py:2400-2455).
        ``context`` pins the keys table when the arguments are literals
        only; ``pw.this.ix_ref(...)`` inside select supplies it
        automatically."""
        from pathway_tpu.internals.expression import wrap_expression

        keys_table = context
        if keys_table is None:
            exprs = [wrap_expression(a) for a in args]
            if instance is not None:
                exprs.append(wrap_expression(instance))
            deps = [d for e in exprs for d in e._dependencies()]
            if not deps:
                raise ValueError(
                    "ix_ref with literal-only keys cannot infer the keys "
                    "table; pass context= or use pw.this.ix_ref(...) "
                    "inside select"
                )
            keys_table = deps[0].table
        # plain strings are literal KEY VALUES here (ix_ref("Alice")),
        # unlike select's string-as-column-name convention
        resolved = [
            wrap_expression(a)
            if isinstance(a, str)
            else resolve_this(a, keys_table)
            for a in args
        ]
        inst = (
            resolve_this(instance, keys_table)
            if instance is not None
            else None
        )
        pointer = PointerExpression(resolved, instance=inst)
        return self.ix(
            pointer,
            optional=optional or allow_misses,
            allow_misses=allow_misses,
            context=keys_table,
        )

    # -- misc ops -----------------------------------------------------------

    def flatten(
        self, to_flatten: Any, *, origin_id: str | None = None, **kwargs: Any
    ) -> "Table":
        """Explode a sequence column; ``origin_id`` names an extra column
        holding the source row's id (reference flatten origin_id)."""
        ref = resolve_this(to_flatten, self)
        assert isinstance(ref, ColumnReference)
        inner = self._dtypes.get(ref.name, dt.ANY)
        base = inner.strip_optional()
        if isinstance(base, dt.List):
            flat_dtype: dt.DType = base.wrapped
        elif isinstance(base, dt.Tuple) and base.args:
            flat_dtype = base.args[0]
        elif base == dt.STR:
            flat_dtype = dt.STR
        else:
            flat_dtype = dt.ANY
        dtypes = {
            n: (flat_dtype if n == ref.name else self._dtypes[n])
            for n in self._column_names
        }
        if origin_id is not None:
            dtypes[origin_id] = dt.Pointer()
        return self._derived(
            TableSpec(
                "flatten", [self], {"column": ref.name, "origin_id": origin_id}
            ),
            dtypes,
        )

    def _gradual_broadcast(
        self,
        threshold_table: "Table",
        lower_column: Any,
        value_column: Any,
        upper_column: Any,
    ) -> "Table":
        """Attach ``apx_value`` moving between lower and upper per row as
        the broadcast value advances (reference table.py:631 over
        operators/gradual_broadcast.rs; used by louvain)."""
        lower = resolve_this(lower_column, threshold_table)
        value = resolve_this(value_column, threshold_table)
        upper = resolve_this(upper_column, threshold_table)
        triplet = threshold_table.select(
            _pw_lower=lower, _pw_value=value, _pw_upper=upper
        )
        return self._derived(
            TableSpec("gradual_broadcast", [self, triplet], {}),
            {
                **{n: self._dtypes[n] for n in self._column_names},
                "apx_value": dt.ANY,
            },
            universe=self._universe,
        )

    def window_join(
        self,
        other: "Table",
        self_time: Any,
        other_time: Any,
        window: Any,
        *on: Any,
        how: str = "inner",
        **kwargs: Any,
    ) -> Any:
        """Reference Table.window_join (_window_join.py:156)."""
        from pathway_tpu.stdlib.temporal import window_join as _wj

        return _wj(
            self, other, self_time, other_time, window, *on, how=how, **kwargs
        )

    @property
    def slice(self) -> "Table":
        """Reference Table.slice — a column-access view; our tables already
        support ``t[...]`` slicing directly."""
        return self

    def having(self, *indexers: Any) -> "Table":
        """Restrict to rows whose id appears among each indexer expression's
        pointer values (reference Table.having, used with ix_ref)."""
        out = self
        for ix in indexers:
            resolved = resolve_this(ix, self)
            keys = resolved.table.select(_pw_p=resolved)
            keys = keys.with_id(keys["_pw_p"])
            out = out.intersect(keys)
        return out

    def sort(self, key: Any, instance: Any = None) -> "Table":
        key_expr = resolve_this(key, self)
        inst_expr = resolve_this(instance, self) if instance is not None else None
        return self._derived(
            TableSpec("sort", [self], {"key": key_expr, "instance": inst_expr}),
            {"prev": dt.Optional_(dt.Pointer()), "next": dt.Optional_(dt.Pointer())},
            universe=self._universe,
        )

    def remove_errors(self) -> "Table":
        return self._derived(
            TableSpec("remove_errors", [self], {}),
            {n: self._dtypes[n] for n in self._column_names},
            universe=self._universe.subset(),
        )

    def await_futures(self) -> "Table":
        # Future columns resolve at commit boundaries in the async executor;
        # at the API level this is a dtype-level unwrap.
        exprs = {
            n: (
                expr_mod.DeclareTypeExpression(
                    ColumnReference(self, n), self._dtypes[n].wrapped
                )
                if isinstance(self._dtypes[n], dt.Future)
                else ColumnReference(self, n)
            )
            for n in self._column_names
        }
        return self._derived(
            TableSpec("select", [self], {"exprs": exprs}),
            {n: e._dtype for n, e in exprs.items()},
            universe=self._universe,
        )

    # -- static constructors ------------------------------------------------

    @staticmethod
    def empty(**kwargs: Any) -> "Table":
        dtypes = {n: dt.wrap(t) for n, t in kwargs.items()}
        return Table(
            TableSpec("static", [], {"rows": []}),
            list(dtypes.keys()),
            dtypes,
        )

    @staticmethod
    def from_rows(
        rows: Sequence[tuple],
        schema: schema_mod.SchemaMetaclass,
        keys: Sequence[Pointer] | None = None,
    ) -> "Table":
        names = schema.column_names()
        dtypes = schema.dtypes()
        pk = schema.primary_key_columns()
        out_rows: list[tuple[Pointer, tuple]] = []
        for i, row in enumerate(rows):
            normalized = tuple(
                dt.normalize_value(v, dtypes[n]) for v, n in zip(row, names)
            )
            if keys is not None:
                key = keys[i]
            elif pk:
                key_vals = tuple(normalized[names.index(p)] for p in pk)
                key = ref_scalar(*key_vals)
            else:
                key = ref_scalar(i)
            out_rows.append((key, normalized))
        return Table(
            TableSpec("static", [], {"rows": out_rows}),
            names,
            dtypes,
        )
