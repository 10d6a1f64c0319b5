"""The ``toy`` family's arithmetic: the unembedding is its one matrix
product, and nothing attends. No jax."""

from __future__ import annotations

DECODE_PROGRAM = "_greedy"


def n_params(config: dict) -> int:
    return (int(config["vocab_size"]) + 1) * int(config["hidden_size"])


def forward_flops(config: dict, tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    return 2.0 * int(config["vocab_size"]) * int(
        config["hidden_size"]) * logit_rows


def decode_step_bytes(config: dict, rows: int, positions: int,
                      counts: dict) -> float:
    """The unembedding and the norm read whole, and of the embedding
    the rows the engine counts as looked up (its own counter, a step's
    mean), in float32; no state."""
    looked_up = counts["serve.engine.rows_looked_up"]
    return 4.0 * (n_params(config) + looked_up * int(config["hidden_size"]))
