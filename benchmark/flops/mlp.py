"""Least work of one ``mlp`` scoring call (bag-of-tokens model): one context
vector and one full-vocabulary distribution per line.

Operations: the pooling adds, the two dense layers and the weight-tied head,
two per multiply-add. gelu and the V exponentials per line are left out, so
the count is a lower bound. Bytes: parameters once in float32, tokens in
(uint16), scores out.
"""
from __future__ import annotations

HIDDEN = 256    # models/mlp.py MLPScorerConfig.hidden; no scorer key sets it


def params_count(scorer: dict) -> int:
    d, v = scorer["dim"], scorer.get("vocab_size", 32768)
    return v * d + d * HIDDEN + HIDDEN + HIDDEN * d + d


def ops_per_row(scorer: dict) -> int:
    d, v, s = scorer["dim"], scorer.get("vocab_size", 32768), scorer["seq_len"]
    return 2 * s * d + 2 * d * HIDDEN + 2 * HIDDEN * d + 2 * d * v


def ops_and_bytes(scorer: dict, rows: int) -> tuple:
    ops = rows * ops_per_row(scorer)
    nbytes = (4 * params_count(scorer) + rows * scorer["seq_len"] * 2
              + rows * 4)
    return ops, nbytes
