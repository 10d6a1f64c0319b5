"""What the ``ouro`` family's algorithm needs, computed from shapes and
never read from the program: parameters, FLOPs of a forward pass and of
a train step, the shape its attention kernels are priced at, and the
bytes a decode step must move. No jax: the driver's process reads it.
Every parameter is active for every token, and every layer attends with
``heads x head_dim``.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}
# the program that makes a decode step, as the device trace names it
DECODE_PROGRAM = "slot_decode_step"


def n_params(config: dict) -> int:
    """Parameters of the model as run (tied embedding counted once)."""
    D, F = int(config["hidden_size"]), int(config["intermediate_size"])
    HD = int(config["num_attention_heads"]) * int(config["head_dim"])
    per_layer = 4 * D * HD + 3 * D * F + 2 * D
    return (int(config["vocab_size"]) * D
            + int(config["num_hidden_layers"]) * per_layer + D)


def attention_flops(config: dict, context_sum: int) -> float:
    """QK^T and PV over all layers: 4 * head_dim * heads FLOPs for each
    (query, key) pair the mask lets through. ``context_sum`` is the
    number of such pairs (for a causal prompt of T tokens T(T+1)/2; for
    a decode step the positions each active row attends)."""
    HD = int(config["num_attention_heads"]) * int(config["head_dim"])
    return 4.0 * HD * int(config["num_hidden_layers"]) * context_sum


def forward_flops(config: dict, tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """2 FLOPs per parameter of the layers for each token, the
    unembedding for the ``logit_rows`` positions whose logits are needed
    (a prefill needs its last one only; the embedding lookup is no
    matrix product), plus attention."""
    unembed = int(config["vocab_size"]) * int(config["hidden_size"])
    return (2.0 * (n_params(config) - unembed) * tokens
            + 2.0 * unembed * logit_rows
            + attention_flops(config, context_sum))


def train_flops(config: dict, batch: int, seq: int) -> float:
    """One optimizer step: 6 * N * tokens plus three times the forward's
    causal attention. Recomputation (remat) is not counted."""
    pairs = batch * seq * (seq + 1) // 2
    return 6.0 * n_params(config) * batch * seq + 3.0 * attention_flops(
        config, pairs)


def flash_shape(config: dict, mix: dict) -> tuple:
    """batch, seq, heads, head_dim, itemsize of a training mix's
    attention calls: what ``peaks.flash_fwd_cost`` and
    ``flash_bwd_cost`` price one call at."""
    return (int(mix["batch"]), int(mix["seq"]),
            int(config["num_attention_heads"]), int(config["head_dim"]),
            ITEMSIZE[config["torch_dtype"]])


def decode_step_bytes(config: dict, rows: int, positions: int,
                      counts: dict) -> float:
    """The bytes one decode step of ``rows`` active rows must move,
    whatever implements it: every parameter read once (the tied head is
    read whole; the ``rows`` rows of the embedding it is tied to are not
    counted again), the K and V of the ``positions`` attended read, and
    those of the ``rows`` new tokens written, in every layer. What a
    program copies beyond that (a cache sliced and written back whole)
    is waste and is not counted. ``counts`` (the program's own counters
    a step) prices nothing here: every parameter is read every step."""
    item = ITEMSIZE[config["torch_dtype"]]
    kv_row = (int(config["num_hidden_layers"])
              * int(config["num_attention_heads"])
              * int(config["head_dim"]) * 2 * item)
    return float(n_params(config) * item + (positions + rows) * kv_row)
