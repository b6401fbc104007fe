"""pw.io.subscribe (reference: python/pathway/io/_subscribe.py:13)."""

from __future__ import annotations

from typing import Any, Callable

from pathway_tpu.engine.graph import Node, Scope
from pathway_tpu.engine.value import Pointer
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.table import Table


def subscribe(
    table: Table,
    on_change: Callable[..., Any] | None = None,
    on_end: Callable[[], Any] | None = None,
    on_time_end: Callable[[int], Any] | None = None,
    *,
    skip_errors: bool = True,
    _internal: bool = False,
) -> None:
    """Call ``on_change(key, row: dict, time, is_addition)`` for every update.

    One sink's callbacks come in order (the rows of a time, its
    ``on_time_end``, the rows of the next), but not always on the thread
    that runs the graph: rows that read a device batch the chip has not
    finished are delivered by the device pipeline's completion worker
    (``engine/device_pipeline.py``), and ``on_time_end`` behind them;
    ``on_end`` comes last, on the run thread."""
    column_names = table.column_names()

    def attach(scope: Scope, node: Node):
        def _on_change(key: Pointer, values: tuple, time: int, diff: int) -> None:
            if on_change is not None:
                row = dict(zip(column_names, values))
                on_change(key=key, row=row, time=time, is_addition=diff > 0)

        scope.subscribe_table(
            node,
            on_change=_on_change,
            on_time_end=on_time_end,
            on_end=on_end,
            skip_errors=skip_errors,
        )
        return None

    G.add_sink(table, attach, internal=_internal)
