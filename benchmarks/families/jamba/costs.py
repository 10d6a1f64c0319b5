"""What the ``jamba`` family's algorithm needs, computed from shapes
and never read from the program: the parameters, the FLOPs of a forward
pass, the cost of a prefill's scan and attention kernel calls and the
bytes a decode step must move. No jax: the driver's process reads it.

A Mamba layer keeps, for each sequence, a state of ``mamba_d_state``
float32 values a channel and the last ``mamba_d_conv - 1`` inputs of
its convolution: a decode step reads and writes both whole, whatever
the sequence's length. An attention layer keeps a row of K and V a
position and reads every one of them.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}
STATE_ITEMSIZE = 4          # the scan's state, and dt that feeds it
# the program that makes a decode step, as the device trace names it
DECODE_PROGRAM = "slot_decode_step"
# FLOPs of the recurrence at one position: for each value of the state
# dt.A, its exp, the decay's product, (dt.u).B, the sum, the product
# with C and the sum over the state; for each channel dt.u, D.u and
# their sum into y
STATE_FLOPS, CHANNEL_FLOPS = 7, 3


def _item(config: dict) -> int:
    return ITEMSIZE[config.get("torch_dtype", "bfloat16")]


def _sizes(config: dict) -> dict:
    D = int(config["hidden_size"])
    return {"D": D, "C": int(config["mamba_expand"]) * D,
            "N": int(config["mamba_d_state"]),
            "R": int(config["mamba_dt_rank"]),
            "K": int(config["mamba_d_conv"]),
            "H": int(config["num_attention_heads"]),
            "G": int(config["num_key_value_heads"]),
            "Dh": D // int(config["num_attention_heads"]),
            "F": int(config["intermediate_size"]),
            "V": int(config["vocab_size"])}


def layer_counts(config: dict) -> tuple:
    """(Mamba layers, attention layers)."""
    period, offset = (int(config["attn_layer_period"]),
                      int(config["attn_layer_offset"]))
    attention = sum(i % period == offset
                    for i in range(int(config["num_hidden_layers"])))
    return int(config["num_hidden_layers"]) - attention, attention


def mamba_matrices(config: dict) -> int:
    """A Mamba mixer's matrix products, in parameters: in, x, dt, out."""
    s = _sizes(config)
    return (s["D"] * 2 * s["C"] + s["C"] * (s["R"] + 2 * s["N"])
            + s["R"] * s["C"] + s["C"] * s["D"])


def mamba_params(config: dict) -> int:
    """A Mamba mixer whole: its matrices, the convolution and its bias,
    the three inner norms, dt's bias, A_log and D."""
    s = _sizes(config)
    return (mamba_matrices(config) + s["K"] * s["C"] + s["C"]
            + s["R"] + 2 * s["N"] + s["C"] + s["N"] * s["C"] + s["C"])


def attention_params(config: dict) -> int:
    s = _sizes(config)
    return 2 * s["D"] * s["H"] * s["Dh"] + 2 * s["D"] * s["G"] * s["Dh"]


def _ffn_params(config: dict) -> int:
    s = _sizes(config)
    return 3 * s["D"] * s["F"]


def n_params(config: dict) -> int:
    """Every parameter: the embedding (which is the head), each layer's
    mixer, SwiGLU and two norms, and the final norm."""
    s = _sizes(config)
    mamba, attention = layer_counts(config)
    return (s["V"] * s["D"] + s["D"]
            + mamba * mamba_params(config)
            + attention * attention_params(config)
            + (mamba + attention) * (_ffn_params(config) + 2 * s["D"]))


def scan_flops(config: dict, tokens: int) -> float:
    """One Mamba layer's convolution and recurrence over ``tokens``
    positions: nothing of it is a matrix product."""
    s = _sizes(config)
    return float(tokens) * s["C"] * (
        2 * s["K"] + s["N"] * STATE_FLOPS + CHANNEL_FLOPS)


def forward_flops(config: dict, tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """2 FLOPs per parameter of the layers' matrices for each token,
    the head for the ``logit_rows`` positions whose logits are needed
    (the embedding lookup is no matrix product), each Mamba layer's
    convolution and recurrence, and QK^T and PV in the attention layers
    for each of the ``context_sum`` (query, key) pairs."""
    s = _sizes(config)
    mamba, attention = layer_counts(config)
    matrices = (mamba * mamba_matrices(config)
                + attention * attention_params(config)
                + (mamba + attention) * _ffn_params(config))
    return (2.0 * matrices * tokens + 2.0 * s["V"] * s["D"] * logit_rows
            + mamba * scan_flops(config, tokens)
            + attention * 4.0 * s["H"] * s["Dh"] * context_sum)


def train_flops(config: dict, batch: int, seq: int) -> float:
    raise NotImplementedError("this family is served only: no cut of it "
                              "within the sizing floors trains on one chip")


def flash_shape(config: dict, mix: dict) -> tuple:
    raise NotImplementedError("this family has no training mix; its "
                              "prefill kernels are priced by "
                              "prefill_scan_costs and prefill_flash_costs")


def prefill_scan_costs(config: dict, length: int) -> list:
    """One prefill's scan kernel calls, a Mamba layer each, as {"flops",
    "bytes"}: the recurrence's FLOPs (the convolution is not the
    kernel's), and u, B and C read and y written at the model's dtype,
    dt read and the final state written in float32."""
    s, item = _sizes(config), _item(config)
    call = {
        "flops": float(length) * s["C"] * (s["N"] * STATE_FLOPS
                                           + CHANNEL_FLOPS),
        "bytes": float(length * (2 * s["C"] + 2 * s["N"]) * item
                       + (length * s["C"] + s["N"] * s["C"])
                       * STATE_ITEMSIZE)}
    return [dict(call) for _ in range(layer_counts(config)[0])]


def prefill_flash_costs(config: dict, length: int) -> list:
    """One prefill's attention kernel calls, an attention layer each:
    the causal pairs' FLOPs, q read and o written at the query's heads,
    k and v read once at the K/V heads, the logsumexp row in float32."""
    s, item = _sizes(config), _item(config)
    pairs = length * (length + 1) // 2
    call = {"flops": 4.0 * s["H"] * s["Dh"] * pairs,
            "bytes": float(2 * length * (s["H"] + s["G"]) * s["Dh"] * item
                           + 4 * s["H"] * length)}
    return [dict(call) for _ in range(layer_counts(config)[1])]


def slot_state_bytes(config: dict) -> int:
    """What one sequence keeps in one Mamba layer between tokens: the
    state in float32 and the convolution's tail at the model's dtype."""
    s = _sizes(config)
    return (s["N"] * s["C"] * STATE_ITEMSIZE
            + (s["K"] - 1) * s["C"] * _item(config))


def decode_step_bytes(config: dict, rows: int, positions: int,
                      counts: dict) -> float:
    """The bytes one decode step of ``rows`` active rows must move,
    whatever implements it: every parameter once (the embedding is the
    head, read whole), each row's state and tail in every Mamba layer
    read and written, and in the attention layers the K and V of the
    ``positions`` attended and of the ``rows`` new tokens written."""
    s = _sizes(config)
    mamba, attention = layer_counts(config)
    kv = (positions + rows) * attention * 2 * s["G"] * s["Dh"]
    return float((n_params(config) + kv) * _item(config)
                 + rows * mamba * slot_state_bytes(config) * 2)
