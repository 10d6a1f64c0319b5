"""The ``toy`` family's arithmetic: the unembedding is its one matrix
product, and nothing attends. No jax."""

from __future__ import annotations


def n_params(config: dict) -> int:
    return (int(config["vocab_size"]) + 1) * int(config["hidden_size"])


def forward_flops(config: dict, tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    return 2.0 * int(config["vocab_size"]) * int(
        config["hidden_size"]) * logit_rows
