"""What the ``brumby`` family's algorithm needs, computed from shapes
and never read from the program: the parameters, the FLOPs of a forward
pass, the cost of a prefill's retention kernel calls, of a decode
step's state pass and the bytes a decode step must move. No jax: the
driver's process reads it.

A power-retention layer (degree 2) keeps, for each sequence and K/V
head, a matrix state of ``D x head_dim`` float32 values and a
normaliser of D, D = head_dim (head_dim + 1) / 2 the size of the
symmetric square of a head (8,256 at 128): a decode step reads and
writes both whole, whatever the sequence's length, and reads no K or V.
The program may hold a few rows more for its kernels' tiling (it holds
8,320); the price here is the algorithm's.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}
STATE_ITEMSIZE = 4          # the state, its normaliser and the gate
# the program that makes a decode step, as the device trace names it
DECODE_PROGRAM = "slot_decode_step"
# positions in a chunk of the chunked form, whose pairs inside a chunk
# go through the attention form (the program's kernel takes the same)
CHUNK = 128


def _item(config: dict) -> int:
    return ITEMSIZE[config.get("torch_dtype", "bfloat16")]


def _sizes(config: dict) -> dict:
    d = int(config["head_dim"])
    return {"D": int(config["hidden_size"]),
            "H": int(config["num_attention_heads"]),
            "G": int(config["num_key_value_heads"]), "d": d,
            "phi": d * (d + 1) // 2,
            "F": int(config["intermediate_size"]),
            "V": int(config["vocab_size"]),
            "L": int(config["num_hidden_layers"])}


def mixer_matrices(config: dict) -> int:
    """A retention layer's matrix products, in parameters: q and o at
    the query heads, k and v at the K/V heads, the gate."""
    s = _sizes(config)
    return (2 * s["D"] * s["H"] * s["d"] + 2 * s["D"] * s["G"] * s["d"]
            + s["D"] * s["G"])


def layer_params(config: dict) -> int:
    """A layer whole: the mixer's matrices, the gate's bias, the q and
    k norms, SwiGLU and the two norms."""
    s = _sizes(config)
    return (mixer_matrices(config) + s["G"] + 2 * s["d"]
            + 3 * s["D"] * s["F"] + 2 * s["D"])


def n_params(config: dict) -> int:
    """Every parameter: the embedding, the untied head, the layers and
    the final norm."""
    s = _sizes(config)
    return 2 * s["V"] * s["D"] + s["D"] + s["L"] * layer_params(config)


def retention_flops(config: dict, tokens: int) -> float:
    """One layer's retention over ``tokens`` positions, past the
    projections: for each position 2 D d for each query head's
    ``phi(q)^T S`` and each K/V head's ``phi(k) v^T``, 2 D for each
    query head's ``phi(q) . z`` and D for each K/V head's z; and the
    pairs inside a chunk, (d + d) multiply-adds a pair and query head
    (the score and its share of the output), some CHUNK / 2 pairs a
    position."""
    s = _sizes(config)
    state = (s["H"] + s["G"]) * 2 * s["phi"] * s["d"] \
        + s["H"] * 2 * s["phi"] + s["G"] * s["phi"]
    pairs = min(CHUNK, tokens) / 2.0
    return float(tokens) * (state + s["H"] * 4 * s["d"] * pairs)


def forward_flops(config: dict, tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """2 FLOPs per parameter of the layers' matrices for each token,
    the head for the ``logit_rows`` positions whose logits are needed
    (the embedding lookup is no matrix product), and each layer's
    retention. ``context_sum`` prices nothing: a position's cost does
    not depend on how many came before it. A decode step (one position
    a row) has no pairs inside a chunk."""
    s = _sizes(config)
    matrices = s["L"] * (mixer_matrices(config) + 3 * s["D"] * s["F"])
    per_layer = retention_flops(config, tokens) if tokens > logit_rows \
        else tokens * retention_flops(config, 1)
    return (2.0 * matrices * tokens + 2.0 * s["V"] * s["D"] * logit_rows
            + s["L"] * per_layer)


def train_flops(config: dict, batch: int, seq: int) -> float:
    raise NotImplementedError("this family is served only: no cut of it "
                              "within the sizing floors trains on one chip")


def flash_shape(config: dict, mix: dict) -> tuple:
    raise NotImplementedError("this family has no attention kernel; its "
                              "kernels are priced by prefill_retention_"
                              "costs and retention_step_costs")


def slot_state_bytes(config: dict) -> int:
    """What one sequence keeps in one layer between tokens: every K/V
    head's state and its normaliser, float32."""
    s = _sizes(config)
    return s["G"] * s["phi"] * (s["d"] + 1) * STATE_ITEMSIZE


def prefill_retention_costs(config: dict, length: int) -> list:
    """One prefill's retention kernel calls, a layer each, as {"flops",
    "bytes"}: q, k, v in and o out at the model's dtype, the gate in
    and the state and its normaliser out once in float32, and the
    retention's FLOPs."""
    s, item = _sizes(config), _item(config)
    call = {"flops": retention_flops(config, length),
            "bytes": float(length * 2 * (s["H"] + s["G"]) * s["d"] * item
                           + length * s["G"] * STATE_ITEMSIZE
                           + slot_state_bytes(config))}
    return [dict(call) for _ in range(s["L"])]


def retention_step_costs(config: dict, rows: int) -> dict:
    """One layer's state pass of a decode step of ``rows`` rows: each
    row's state and normaliser read and written once, its q, k, v and o
    beside them, and one position's retention a row."""
    s, item = _sizes(config), _item(config)
    return {"flops": rows * retention_flops(config, 1),
            "bytes": float(rows * (2 * slot_state_bytes(config)
                                   + 2 * (s["H"] + s["G"]) * s["d"] * item
                                   + s["G"] * STATE_ITEMSIZE))}


def decode_step_bytes(config: dict, rows: int, positions: int,
                      counts: dict) -> float:
    """The bytes one decode step of ``rows`` active rows must move,
    whatever implements it: every layer weight and the head once at the
    served itemsize (of the embedding the looked-up rows alone), and
    each row's state in every layer read and written once.
    ``positions`` price nothing: the state is the context."""
    s = _sizes(config)
    weights = n_params(config) - s["V"] * s["D"] + rows * s["D"]
    return float(weights * _item(config)
                 + rows * s["L"] * slot_state_bytes(config) * 2)
