#!/usr/bin/env python3
"""The hand run of the window at the published widths, outside any cell:

    python3 benchmark/dev/window_command_a.py [seed]

``command-a-plus-rag-answerer``'s decoder (its share of the experts, its
weights from the seed) behind a ``TpuPipelineChat`` of 2 rows whose one
prompt bucket is 6,144 tokens: prompts of 5,000 and 6,144 tokens,
left-padded to 6,144, 64 new tokens through the chat's own ``chat_prefill``
and ``chat_decode``. Both prompts are longer than ``sliding_window`` (4,096):
prefill keeps the last 4,096 positions in each sliding layer's ring, every
decode step wraps it, and the full layer keeps all 6,208. What was served is
compared as ``check_command_a`` compares a cell's: the reference
(``reference_command_a.py``, float32 at ``highest``, no cache, the window a
mask, in blocks) over prompt + served tokens; ``served_logit_gap`` of the
first token and of the decode steps, ``greedy_gap``, the widest step. One
JSON line; no limit is applied here (``PERF.md`` has the readings beside the
cell's limits).
"""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import run  # noqa: E402

run.configure_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import check_decoder  # noqa: E402
import reference_command_a as refcmd  # noqa: E402
from pathway_tpu.models.decoder import DecoderConfig  # noqa: E402
from pathway_tpu.xpacks.llm.llms import TpuPipelineChat  # noqa: E402

LENGTHS, WIDTH, NEW = (5000, 6144), 6144, 64


def main(seed: int) -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"window_command_a: needs a TPU chip; JAX reports {device.platform!r}", file=sys.stderr)
        return 2
    with open(os.path.join(BENCH, "configs", "command-a-plus-rag-answerer.json")) as fh:
        dec = json.load(fh)
    params = refcmd.make_params(seed, dec)
    chat = TpuPipelineChat(
        DecoderConfig.from_hf(dec), max_new_tokens=NEW, max_prompt_len=WIDTH, max_batch_size=len(LENGTHS),
        prompt_buckets=[WIDTH], params=params, eos_id=None, cache_tag="window",
    )
    rng = np.random.default_rng([seed, 7])
    prompts = [rng.integers(4, dec["vocab_size"], n).tolist() for n in LENGTHS]
    ids, mask = np.zeros((len(LENGTHS), WIDTH), np.int32), np.zeros((len(LENGTHS), WIDTH), bool)
    for row, prompt in enumerate(prompts):
        ids[row, WIDTH - len(prompt) :], mask[row, WIDTH - len(prompt) :] = prompt, True
    cache, offset, first, first_logit, _ = chat._prefill(jnp.asarray(ids), jnp.asarray(mask))
    slots = [state["k"].shape[1] for state in cache.layers]
    rest, rest_logits, _ = chat._decode(cache, offset, first, jnp.ones(len(LENGTHS), bool))
    tokens = np.concatenate([np.asarray(first)[:, None], np.asarray(rest)], axis=1)
    logits = np.concatenate([np.asarray(first_logit)[:, None], np.asarray(rest_logits)], axis=1)
    del cache, chat
    sample = [{"tokens": [int(t) for t in tokens[row]], "logits": logits[row].astype(np.float64)} for row in range(len(LENGTHS))]
    reference = refcmd.served_logits(params, [(p, s["tokens"]) for p, s in zip(prompts, sample)], dec, WIDTH + NEW)
    got = check_decoder.gaps(reference, sample)
    served, greedy = got["served"], got["greedy"]
    print(json.dumps({
        "seed": seed, "device": device.device_kind, "prompt_tokens": LENGTHS, "new_tokens": NEW, "cache_slots_by_layer": slots,
        "served_logit_gap.prefill": float(served[:, 0].mean()), "served_logit_gap.decode": float(served[:, 1:].mean()),
        "greedy_gap": float(greedy.mean()), "served_logit_gap_widest_step": float(served.max()),
        "served_logit_gap_by_row": [float(v) for v in served.mean(1)],
        "served_tokens_that_are_the_references_best": float(np.mean(greedy == 0)),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 2147483659))
