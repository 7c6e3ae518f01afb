"""Operations a step needs, from a configuration's sizes, and the peaks.

Counted as the algorithm needs them: recomputation (remat) and the coded
step's redundant slot copies do not count.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def dense_lm_matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and the LM head; the embedding is a gather and
    the norm gains are elementwise, so neither counts."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    head_dim = d // heads
    kv = int(config["num_key_value_heads"]) * head_dim
    f = int(config["intermediate_size"])
    layer = d * heads * head_dim + 2 * d * kv + heads * head_dim * d \
        + 3 * d * f
    return int(config["num_hidden_layers"]) * layer \
        + d * int(config["vocab_size"])


def dense_lm_train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per trained token:
    6 × matmul parameters, plus causal attention's 6·L·S·H·D (the
    forward's q·kᵀ and p·v over S/2 keys on average, times three)."""
    d = int(config["hidden_size"])
    L = int(config["num_hidden_layers"])
    return 6.0 * dense_lm_matmul_params(config) + 6.0 * L * seq_len * d


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]
