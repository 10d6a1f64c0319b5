"""What the ``phi4flash`` family's algorithm needs, computed from
shapes and never read from the program: the parameters, the FLOPs of a
forward pass, the cost of a prefill's scan and attention kernel calls,
of a decode step's attention kernel calls, and the bytes a decode step
must move. No jax: the driver's process reads it.

The self-decoder's Mamba layers keep, for each sequence, a state of
``mamba_d_state`` float32 values a channel and the last ``mamba_d_conv
- 1`` inputs of the convolution; its window layers the K and V of the
last ``sliding_window`` positions. **One layer, the full-attention
layer behind the self-decoder, keeps a row of K and V a position, and
it is read by itself and by every cross layer**: a decode step reads
it once a reader. The cross-decoder's layers keep nothing, and run
only at the positions whose logits are wanted: a prefill runs them at
its last position alone.
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
    D, assumed = int(config["hidden_size"]), config["assumed_sizes"]
    return {"D": D, "C": int(assumed["mamba_expand"]) * D,
            "N": int(assumed["mamba_d_state"]),
            "R": int(assumed["mamba_dt_rank"]),
            "K": int(assumed["mamba_d_conv"]),
            "H": int(config["num_attention_heads"]),
            "G": int(config["num_key_value_heads"]),
            "Dh": D // int(config["num_attention_heads"]),
            "F": int(config["intermediate_size"]),
            "V": int(config["vocab_size"]),
            "W": int(config["sliding_window"])}


def layer_counts(config: dict) -> dict:
    """How many layers of each mixer: the rule of ``reference.mixers_of``
    counted (no jax here, so not imported)."""
    layers, half = int(config["num_hidden_layers"]), int(
        config["num_hidden_layers"]) // 2
    every = int(config["mb_per_layer"])
    mamba = sum(i % every == 0 and i <= half for i in range(layers))
    gmu = sum(i % every == 0 and i > half for i in range(layers))
    window = sum(i % every != 0 and i < half for i in range(layers))
    return {"mamba": mamba, "gmu": gmu, "window": window, "full": 1,
            "cross": layers - mamba - gmu - window - 1}


def mamba_matrices(config: dict) -> int:
    """A Mamba mixer's matrix products, in parameters: in, x, dt, out."""
    s = _sizes(config)
    return (s["D"] * 2 * s["C"] + s["C"] * (s["R"] + 2 * s["N"])
            + s["R"] * s["C"] + s["C"] * s["D"])


def mamba_params(config: dict) -> int:
    """A Mamba mixer whole: its matrices, the convolution and its bias,
    dt's bias, A_log and D."""
    s = _sizes(config)
    return (mamba_matrices(config) + s["K"] * s["C"] + s["C"]
            + s["C"] + s["N"] * s["C"] + s["C"])


def gmu_params(config: dict) -> int:
    s = _sizes(config)
    return 2 * s["D"] * s["C"]


def _differential_params(config: dict) -> int:
    """lambda's four vectors and the norm over a pair's output."""
    return 4 * _sizes(config)["Dh"] + 2 * _sizes(config)["Dh"]


def attention_matrices(config: dict) -> int:
    s = _sizes(config)
    return 2 * s["D"] * s["H"] * s["Dh"] + 2 * s["D"] * s["G"] * s["Dh"]


def attention_params(config: dict) -> int:
    """Matrices, their four biases, lambda and the pair norm."""
    s = _sizes(config)
    return (attention_matrices(config) + s["H"] * s["Dh"]
            + 2 * s["G"] * s["Dh"] + s["D"] + _differential_params(config))


def cross_matrices(config: dict) -> int:
    s = _sizes(config)
    return 2 * s["D"] * s["H"] * s["Dh"]


def cross_params(config: dict) -> int:
    s = _sizes(config)
    return (cross_matrices(config) + s["H"] * s["Dh"] + s["D"]
            + _differential_params(config))


def _ffn_params(config: dict) -> int:
    s = _sizes(config)
    return 3 * s["D"] * s["F"]


def n_params(config: dict) -> int:
    """Every parameter: the embedding (which is the head), each layer's
    mixer, SwiGLU and two LayerNorms (scale and bias), and the final
    LayerNorm."""
    s, n = _sizes(config), layer_counts(config)
    return (s["V"] * s["D"] + 2 * s["D"]
            + n["mamba"] * mamba_params(config)
            + n["gmu"] * gmu_params(config)
            + (n["window"] + n["full"]) * attention_params(config)
            + n["cross"] * cross_params(config)
            + sum(n.values()) * (_ffn_params(config) + 4 * s["D"]))


def scan_flops(config: dict, tokens: int) -> float:
    """One Mamba layer's convolution and recurrence over ``tokens``
    positions: nothing of it is a matrix product."""
    s = _sizes(config)
    return float(tokens) * s["C"] * (
        2 * s["K"] + s["N"] * STATE_FLOPS + CHANNEL_FLOPS)


def window_pairs(config: dict, tokens: int, context_sum: int) -> int:
    """The (query, key) pairs a window layer lets through, of
    ``context_sum`` that a full layer does: a query sees at most the
    window. A whole causal prompt (``context_sum`` = T(T+1)/2) is
    counted exactly; its first positions see fewer."""
    W = _sizes(config)["W"]
    if context_sum == tokens * (tokens + 1) // 2:
        short = min(tokens, W)
        return short * (short + 1) // 2 + (tokens - short) * W
    return min(context_sum, tokens * W)


def pair_flops(config: dict) -> float:
    """FLOPs of one (query position, key position) pair in one
    attention or cross layer: every query head's product with its own
    key (2 Dh) and its weight on the doubled value (2 x 2 Dh)."""
    s = _sizes(config)
    return 6.0 * s["H"] * s["Dh"]


def forward_flops(config: dict, tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """The two stages priced apart. Over all ``tokens`` positions: 2
    FLOPs per parameter of the matrices of the Mamba and window layers
    and their feed-forwards, each Mamba layer's convolution and
    recurrence, the window layers' pairs, and of the full layer its K
    and V projections alone. At the ``logit_rows`` positions whose
    logits are needed (every row of a decode step, the last position
    of a prefill): what is left of the full layer (q, the output
    projection, the feed-forward) and the cross-decoder, gated memory
    units and cross layers with their feed-forwards, and the head. The
    full layer's and the cross layers' pairs: for each of those
    positions its context, ``context_sum`` in a decode step, ``tokens``
    for the one position of a prefill."""
    s, n = _sizes(config), layer_counts(config)
    kv = 2 * s["D"] * s["G"] * s["Dh"]
    everywhere = (n["mamba"] * mamba_matrices(config)
                  + n["window"] * attention_matrices(config) + kv
                  + (n["mamba"] + n["window"]) * _ffn_params(config))
    wanted = (attention_matrices(config) - kv
              + n["gmu"] * gmu_params(config)
              + n["cross"] * cross_matrices(config)
              + (1 + n["gmu"] + n["cross"]) * _ffn_params(config)
              + s["V"] * s["D"])
    attended = context_sum if logit_rows == tokens else tokens * logit_rows
    return (2.0 * everywhere * tokens + 2.0 * wanted * logit_rows
            + n["mamba"] * scan_flops(config, tokens)
            + pair_flops(config) * (
                n["window"] * window_pairs(config, tokens, context_sum)
                + (1 + n["cross"]) * attended))


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
    return [dict(call) for _ in range(layer_counts(config)["mamba"])]


def prefill_flash_costs(config: dict, length: int) -> list:
    """One prefill's attention kernel calls, a window layer each (the
    full layer's one query, the prompt's last, is no kernel's): the
    FLOPs of the pairs inside the band, q, k and v read once and both
    softmaxes' outputs over the doubled value written, the logsumexp
    row in float32."""
    s, item = _sizes(config), _item(config)
    pairs = window_pairs(config, length, length * (length + 1) // 2)
    call = {"flops": pair_flops(config) * pairs,
            "bytes": float(length * (s["H"] * s["Dh"] + 2 * s["G"] * s["Dh"]
                                     + s["H"] * 2 * s["Dh"]) * item
                           + 4 * s["H"] * length)}
    return [dict(call) for _ in range(layer_counts(config)["window"])]


def kv_row_bytes(config: dict) -> int:
    """K and V of one position in one attention layer."""
    s = _sizes(config)
    return 2 * s["G"] * s["Dh"] * _item(config)


def decode_attend_costs(config: dict, rows: float, positions: float) -> list:
    """One decode step's calls of the kernel over the one growing
    cache, the full layer's and each cross layer's, as {"flops",
    "bytes"}: the K and V bytes of the ``positions`` its ``rows``
    attend together (and their queries and outputs, which are nothing
    beside them), the pairs' FLOPs."""
    s, item = _sizes(config), _item(config)
    call = {"flops": pair_flops(config) * positions,
            "bytes": float(positions * kv_row_bytes(config)
                           + rows * s["H"] * 3 * s["Dh"] * item)}
    return [dict(call) for _ in range(1 + layer_counts(config)["cross"])]


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
    read and written, each row's window of K and V in every window
    layer (a row whose context is shorter has no such cell), **the K
    and V of the ``positions`` attended in the one growing cache once
    for each layer that reads it**, the full layer and every cross
    layer, and the ``rows`` new tokens' K and V written in the window
    and full layers."""
    s, n = _sizes(config), layer_counts(config)
    kv = kv_row_bytes(config) * (
        n["window"] * rows * s["W"] + (1 + n["cross"]) * positions
        + (n["window"] + 1) * rows)
    return float(n_params(config) * _item(config) + kv
                 + rows * n["mamba"] * slot_state_bytes(config) * 2)
