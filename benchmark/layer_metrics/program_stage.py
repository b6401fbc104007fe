"""Reader of the per-layer metrics that come from the program's own stage
table (``pathway_tpu.internals.tracing.stage_totals()``): one sum of
stage fields over another. The table covers the whole ``pw.run()``, the
primers and the drain after the window's close included; warm-up and
prefill run before ``pw.run()`` begins and are not in it.

A term is ``[-]<stage>[?][:<field>]``:

- ``<stage>`` is a stage of the run's thread by name, or by ``fnmatch``
  pattern (``op.*``: every stage that matches, at least one), or
  ``@wait``: every stage whose exit blocks on the device;
- ``<field>`` is ``calls``, ``total_ns``, ``self_ns`` or one of the
  stage's counts (``rows``, ``h2d_bytes``...), and the metric's ``field``
  where the term names none; ``@run_wall_ns`` is the run's wall;
- ``-`` subtracts the term; ``?`` lets the stage be absent (a stall that
  never happened), where any other absent stage ends the reading.

The reading is ``scale * sum(numerator) / sum(denominator)``, or ``None``
where the program has no stage table (a commit from before it), a stage a
term names is absent, or the denominator is 0.
"""

from __future__ import annotations

import fnmatch


def _term(term: str, totals: dict, field: str):
    sign = 1.0
    if term.startswith("-"):
        sign, term = -1.0, term[1:]
    if term == "@run_wall_ns":
        return sign * totals["run_wall_ns"]
    name, _, own_field = term.partition(":")
    optional = name.endswith("?")
    name = name.rstrip("?")
    stages = totals["stages"]
    if name == "@wait":
        rows, optional = [row for row in stages.values() if row["wait"]], True
    else:
        rows = [row for stage, row in stages.items() if fnmatch.fnmatchcase(stage, name)]
    if not rows and not optional:
        return None
    key = own_field or field
    return sign * sum(row[key] if key in row else row["counts"].get(key, 0) for row in rows)


def read(ctx, numerator: list[str], denominator: list[str], scale: float = 1.0, field: str = "total_ns"):
    from pathway_tpu.internals import tracing

    stage_totals = getattr(tracing, "stage_totals", None)
    if stage_totals is None:
        return None
    totals = stage_totals()
    sums = []
    for terms in (numerator, denominator):
        values = [_term(term, totals, field) for term in terms]
        if any(value is None for value in values):
            return None
        sums.append(sum(values))
    if sums[1] == 0:
        return None
    return scale * sums[0] / sums[1]
