#!/usr/bin/env python3
"""Readings for the limits of ``correct`` in ``mellum2-rag-answer-long``
(the controls of ``check_mellum.py``'s gaps), on the chip, at the cell's
own size:

    python3 benchmark/control_mellum.py --workload <cell> --seeds 1,2,3 --seconds 8

``control_lfm2.py``'s procedure with this model's reference and four
controls: for each seed, in one process, the cell's set-up and a short
window at the cell's own load; the program's reading of every number
compared (the lower readings); then each control — the reference put in the
program's place, one corner cut — through the same ``check_mellum.compare()``,
which has to say ``correct: false`` for every one (the upper readings):

- ``control_float8_experts``: float8 (e4m3) operands in the routed experts'
  products, the step below the bfloat16 the configuration states;
- ``control_sliding_as_full``: the sliding layers' window left out;
- ``control_full_without_yarn``: the full layer turned by plain RoPE;
- ``control_no_attention_factor``: YaRN's frequencies, cos and sin unscaled.

One JSON line a seed. The benchmark's own runs never run this;
``benchmark/tests/test_rag_answerer_mellum.py`` keeps it at a size a test
can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(cell, seed: int, seconds: float, devices) -> dict:
    """The program's verdict and each control's, for one seed."""
    import check
    import check_decoder
    import check_mellum
    import harness
    import reference_mellum as refmel
    from control import _verdict

    _, evidence = harness.measure(cell, seed, seconds, False, devices, time.time())
    memo: dict = {}
    program = check.compare(cell, seed, **evidence) + check_mellum.compare(cell, seed, **evidence, memo=memo)
    out = {"seed": seed, "program": _verdict(program)}
    widest = {"program": None}
    controls = {"control_float8_experts": {"operand": refmel.quantize_fp8}}
    controls.update({f"control_{cut}": {"cut": cut} for cut in refmel.CUTS})
    for name, kwargs in controls.items():
        low = check_mellum.reference_logits(cell, evidence["facts"], memo["sample"], **kwargs)
        out[name] = _verdict(check_mellum.compare(cell, seed, **evidence, stand_in=low, memo=memo))
        widest[name] = low
    for name, stand_in in widest.items():
        got = check_decoder.gaps(memo["logits"], memo["sample"], stand_in)
        out[name]["served_logit_gap_widest"] = float(got["served"].max())
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--seconds", type=float, default=8.0)
    args = parser.parse_args(argv)
    sys.path[:0] = [HERE, ROOT]
    import run

    run.configure_compile_cache()
    import harness
    import jax

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {args.workload} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
