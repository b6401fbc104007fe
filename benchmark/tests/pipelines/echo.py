"""A pipeline that lives only in the tests: no model and no index; every
event comes back at its sink as it was sent. It shows that a configuration
with another graph runs through ``harness.run_cell`` by files alone."""

from __future__ import annotations


def echo(text: str) -> str:
    return text


def weights(cell, seed: int) -> dict:
    return {"echo": echo}


def set_up(cell, seed: int, schedule, state: dict, mesh, phase) -> None:
    phase("warm_up")


def build(pw, cell, state: dict, feeds: dict, clock) -> None:
    for stream, feed in feeds.items():
        if feed is None:
            continue
        field = getattr(clock.obs, stream).field
        kept = clock.obs.evidence[stream] = {}
        table = pw.io.python.read(
            feed,
            schema=pw.schema_from_types(**{field: int, "text": str}),
            autocommit_duration_ms=cell.config["autocommit_ms"],
        )
        table = table.select(pw.this[field], back=pw.apply(state["echo"], pw.this.text))
        pw.io.subscribe(
            table,
            on_change=clock.sink(stream, lambda i, key, row, kept=kept: kept.__setitem__(i, row["back"])),
            on_time_end=clock.on_time_end,
        )


def restore(state: dict) -> None:
    pass


def work_flops(cell, schedule, obs) -> float:
    return 0.0


def facts(cell, state: dict, obs, seed: int, schedule) -> dict:
    return {"state": sorted(state)}


def compare(cell, seed: int, *, schedule, obs, facts: dict) -> list[dict]:
    numbers = []

    def exact(name: str, value) -> None:
        numbers.append({"name": name, "value": int(value), "limit": 0, "ok": int(value) == 0})

    for stream in ("documents", "queries"):
        plan, seen = getattr(schedule, stream), getattr(obs, stream)
        if plan is None:
            continue
        texts = plan.texts + plan.texts[:1]  # the primer is the first text again
        kept = obs.evidence[stream]
        exact(f"{stream}_lost", seen.n_sent() - seen.acked)
        exact(f"{stream}_repeated", seen.repeats)
        exact(f"{stream}_altered", sum(back != texts[i] for i, back in kept.items()))
    exact("error_log", len(obs.errors))
    exact("compiles_in_window", facts["compiles_in_window"])
    return numbers
