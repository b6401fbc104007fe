"""Reader of the chat programs' shares of their rooflines: the least time the
chip could take for a call of ``jit_chat_prefill`` or ``jit_chat_decode``
(``costs_decoder.py``, from the shapes the pipeline saw dispatched in the
traced part of the window and the counters each call brought back) over the
device time of that program's executions in the trace.

Both sides are means a call, not sums: a call lasts tenths of a second and
the traced part four seconds, so a call begun just before the trace ends
would add its least time to a sum and none of its device time.

``None`` where there is no trace, no execution of the program in it, or the
pipeline recorded no chat call (a program from before the chat had names).
"""

from __future__ import annotations

import costs
import costs_decoder
import trace as trace_mod


def _least_prefill(call, dec, peak) -> float:
    _, _, bucket, _rows, prefill_touched, _, _ = call
    batch = dec["chat"]["max_batch_size"]  # a call always pads its rows to the cap
    seconds, _ = costs.roofline_seconds(
        costs_decoder.prefill_flops(batch, bucket, dec),
        costs_decoder.prefill_bytes(batch, bucket, dec, prefill_touched), peak,
    )
    return seconds


def _least_decode(call, dec, peak) -> float:
    """The steps of one call: every step's resident bytes and flops at its
    own context, the routed experts' bytes by the touched count the call
    brought back (summed over steps and layers)."""
    _, _, bucket, _rows, _, decode_touched, _ = call
    batch, steps = dec["chat"]["max_batch_size"], dec["chat"]["max_new_tokens"] - 1
    flops = sum(costs_decoder.decode_step_flops(batch, bucket + j, dec) for j in range(1, steps + 1))
    nbytes = sum(costs_decoder.decode_step_bytes(batch, bucket + j, dec, 0) for j in range(1, steps + 1))
    nbytes += costs_decoder.PARAM_BYTES * decode_touched * costs_decoder.expert_params(dec)
    return costs.roofline_seconds(flops, nbytes, peak)[0]


def read(ctx, program: str, patterns: list[str]):
    if ctx.trace is None or ctx.peak is None:
        return None
    seconds, executions = trace_mod.module_seconds(ctx.trace["events"], patterns)
    start, stop = ctx.trace["start"], ctx.trace["stop"]
    calls = [c for c in ctx.obs.device_calls if c[1] == "chat" and start <= c[0] <= stop]
    if seconds <= 0 or not executions or not calls:
        return None
    least = _least_prefill if program == "prefill" else _least_decode
    dec = ctx.cell.config
    mean_least = sum(least(c, dec, ctx.peak) for c in calls) / len(calls)
    return 100.0 * mean_least / (seconds / executions)
