"""A toy cell that lives only in the tests: the MiniLM preset at 16 tokens,
a 1,024-row index, a few dozen events a second."""

from __future__ import annotations

import copy
import json
import os
import time

import harness
import reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOKENS = {"dist": "uniform", "min": 8, "max": 16}
MIXES = {
    "rag": {
        "vocabulary_words": 50,
        "documents": {"loop": "open", "arrivals": "poisson", "rate_per_s": 40, "tokens": TOKENS},
        "queries": {
            "loop": "open", "arrivals": "poisson", "rate_per_s": 30,
            "burst": {"on_s": 0.5, "off_s": 0.5}, "autocommit_ms": 50, "tokens": TOKENS,
        },
    },
    "backfill": {
        "vocabulary_words": 50,
        "documents": {"loop": "closed", "in_flight": 32, "pool_per_s": 400, "tokens": TOKENS},
        "queries": None,
    },
}
#: CPU readings at this size: the median gaps 0.003-0.004 (bfloat16 against
#: float32), the widest row under 0.01, knn_gap under 1e-6; the float8
#: control's medians read 0.03
LIMITS = {"embed_gap_docs": 0.012, "embed_gap_queries": 0.012, "embed_row_limit": 0.03, "knn_gap": 5e-6}


def cell(mix: str, chips: int = 1) -> harness.Cell:
    reference.PREFILL_BLOCK = 16
    with open(os.path.join(BENCH, "configs", "minilm-l6-live-index.json")) as fh:
        config = json.load(fh)
    config["embedder"] = {"max_len": 16, "max_batch_size": 16, "seq_bucket_min": 8}
    config["index"].update(capacity=1024, prefilled=480)
    e2e = [{"name": n, "unit": "x"} for n in
           ("setup_s", "docs_per_s", "index_lag_p95_ms", "query_p50_ms")]
    return harness.Cell(
        f"toy-{mix}", chips, config, copy.deepcopy(MIXES[mix]), dict(LIMITS), e2e, [],
        harness.find_pipeline(config.get("pipeline", harness.DEFAULT_PIPELINE)),
    )


def run(mix: str, seed: int = 2**31 + 7, seconds: float = 2.0, chips: int = 1, trace: bool = False):
    import jax

    return harness.run_cell(cell(mix, chips), seed, seconds, trace, jax.devices(), time.time())
