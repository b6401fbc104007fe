"""Reader of the chat programs' shares of their rooflines under a ``mellum``
configuration: ``chat_roofline_lfm2.py``'s procedure over this model's
costs. The least time the chip could take for what a call of
``jit_chat_prefill`` or ``jit_chat_decode`` **held** — its real rows, their
real tokens, the pairs its experts took and the experts it touched, as
``pipelines/rag_answerer_mellum.py`` recorded them from what the call brought
back (``costs_mellum.py``: a sliding layer's scores over its window alone, a
ring read at the slots a step sees) — over the device time of that call's
own executions: the pipeline puts a host span ``bench:mellum_call.<n>``
round call ``n`` of ``chat_calls``, and the program's executions whose
middle lies inside the span are that call's. Prefill's least time is its
compute's, decode's its bytes'; ``costs.roofline_seconds`` takes the larger.
Padding and the scores a prefill walks outside a window count nothing on
the upper side and all they cost on the lower, so no sound change can read
over 100%.

``None`` where there is no trace, no call whose span and executions are both
whole in it, or the pipeline recorded no call.
"""

from __future__ import annotations

import sys

import costs
import costs_mellum as cost
import trace as trace_mod

#: the host span the pipeline puts round call ``n`` of ``chat_calls``
CALL_SPAN = trace_mod.SPAN_PREFIX + "mellum_call."


def least_seconds(call: dict, program: str, dec: dict, peak: dict) -> float:
    tokens, steps = call["prompt_tokens"], dec["chat"]["max_new_tokens"] - 1
    if program == "prefill":
        flops = cost.prefill_flops(tokens, dec, call["prefill_pairs_held"])
        nbytes = cost.prefill_bytes(tokens, dec, call["prefill_touched"])
    else:
        flops = cost.decode_flops(tokens, steps, dec, call["decode_pairs_held"])
        nbytes = cost.decode_bytes(tokens, steps, dec, call["decode_touched"])
    return costs.roofline_seconds(flops, nbytes, peak)[0]


def read(ctx, program: str, patterns: list[str]):
    if ctx.trace is None or ctx.peak is None:
        return None
    events, calls = ctx.trace["events"], ctx.obs.evidence.get("chat_calls", ())
    chips = set(trace_mod.device_planes(events))
    runs = [
        e for e in events
        if e.plane in chips and e.line == "XLA Modules" and any(p in e.name for p in patterns)
    ]
    least = seconds = 0.0
    shares = []
    for span in events:
        place = int(span.name[len(CALL_SPAN):]) if span.name.startswith(CALL_SPAN) else len(calls)
        if place >= len(calls):
            continue
        call = calls[place]
        took = sum(
            e.dur_ns for e in runs if span.start_ns <= e.start_ns + e.dur_ns / 2 <= span.start_ns + span.dur_ns
        ) / 1e9
        if took > 0:
            floor = least_seconds(call, program, ctx.cell.config, ctx.peak)
            least, seconds = least + floor, seconds + took
            shares.append(f"{call['rows']} rows {100 * floor / took:.1f}%")
    if seconds <= 0:
        return None
    print(f"chat {program} roofline, a matched call: " + ", ".join(shares), file=sys.stderr)
    return 100.0 * least / seconds
