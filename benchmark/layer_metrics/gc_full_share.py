"""Reader of the share of the run's wall that full garbage collections
took: the ``gc.full`` stage (``pathway_tpu.internals.tracing``: a generation
2 collection, on whichever thread the collector ran; the interpreter stands
still for all of it) summed over every thread's table, over the run's wall,
in %. ``program_stage`` reads the run thread's rows alone, and a collection
the feed's thread set off is in that thread's table.

0.0 where no full collection ran; ``None`` where the program has no such
stage (a commit from before it: nothing named the collector, which is not
the same as no collection).
"""

from __future__ import annotations


def read(ctx):
    from pathway_tpu.internals import tracing

    stage = getattr(tracing, "GC_STAGE", None)
    if stage is None:
        return None
    totals = tracing.stage_totals()
    if not totals["run_wall_ns"]:
        return None
    tables = [totals["stages"], *totals["threads"].values()]
    took = sum(table[stage]["total_ns"] for table in tables if stage in table)
    return 100.0 * took / totals["run_wall_ns"]
