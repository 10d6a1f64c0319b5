"""What the ``mimo_v2_flash`` family's algorithm needs, computed from
shapes and never read from the program: the parameters this chip holds,
the FLOPs of a forward pass, the cost of a prefill's attention kernels
and the bytes a decode step must move. No jax: the driver's process
reads it.

The chip holds ``n_routed_experts`` of the router's ``router_experts``
experts in each expert layer; a row's ``num_experts_per_tok`` choices
fall on a held expert ``held / router`` of the time, which is the
expected work counted here. Full layers attend every earlier position;
window layers at most ``sliding_window`` of them.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}
# the program that makes a decode step, as the device trace names it
DECODE_PROGRAM = "slot_decode_step"
# the counter the engine keeps of the held experts a step read
EXPERTS_HIT = "serve.engine.experts_hit"


def _layers(config: dict):
    """[(is a window layer, is an expert layer)] in the layers' order."""
    return [(bool(w), bool(e)) for w, e in zip(
        config["hybrid_layer_pattern"], config["moe_layer_freq"])]


def _kv_heads(config: dict, window: bool) -> int:
    return int(config["swa_num_key_value_heads" if window
                      else "num_key_value_heads"])


def _attention_params(config: dict, window: bool) -> int:
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    Dqk, Dv = int(config["head_dim"]), int(config["v_head_dim"])
    G = _kv_heads(config, window)
    sink = H if config.get("add_swa_attention_sink_bias" if window
                           else "add_full_attention_sink_bias") else 0
    return D * H * Dqk + D * G * (Dqk + Dv) + H * Dv * D + sink


def expert_params(config: dict) -> int:
    """One expert's three matrices."""
    return 3 * int(config["hidden_size"]) * int(
        config["moe_intermediate_size"])


def experts_held(config: dict) -> tuple:
    """(experts held in an expert layer, expert layers)."""
    return (int(config["n_routed_experts"]),
            sum(e for _, e in _layers(config)))


def _router_params(config: dict) -> int:
    return (int(config["hidden_size"]) + 1) * int(config["router_experts"])


def _outside_experts(config: dict) -> int:
    """Every parameter of the layers but the experts' own: attention,
    norms, the dense feed-forward, the routers with their bias."""
    D, total = int(config["hidden_size"]), 0
    for window, experts in _layers(config):
        total += _attention_params(config, window) + 2 * D
        total += (_router_params(config) if experts
                  else 3 * D * int(config["intermediate_size"]))
    return total + D            # the final norm


def n_params(config: dict) -> int:
    """Parameters this chip holds: embedding, untied head, every layer's
    attention, the dense feed-forward, and the held experts."""
    held, layers = experts_held(config)
    return (2 * int(config["vocab_size"]) * int(config["hidden_size"])
            + _outside_experts(config)
            + layers * held * expert_params(config))


def window_pairs(config: dict, tokens: int, context_sum: int) -> int:
    """The (query, key) pairs a window layer lets through, of
    ``context_sum`` that a full layer does: a query sees at most the
    window. A whole causal prompt (``context_sum`` = T(T+1)/2) is
    counted exactly; its first positions see fewer."""
    W = int(config["sliding_window"])
    if context_sum == tokens * (tokens + 1) // 2:
        short = min(tokens, W)
        return short * (short + 1) // 2 + (tokens - short) * W
    return min(context_sum, tokens * W)


def attention_flops(config: dict, tokens: int, context_sum: int) -> float:
    """QK^T and PV over all layers: 2 * heads * (q.k width + v width)
    FLOPs for each (query, key) pair the layer's mask lets through."""
    per_pair = 2.0 * int(config["num_attention_heads"]) * (
        int(config["head_dim"]) + int(config["v_head_dim"]))
    in_window = window_pairs(config, tokens, context_sum)
    return per_pair * sum(in_window if window else context_sum
                          for window, _ in _layers(config))


def forward_flops(config: dict, tokens: int, context_sum: int,
                  logit_rows: int) -> float:
    """2 FLOPs per active parameter of the layers for each token (of
    the held experts the expected ``k * held / router`` a token), the
    head for the ``logit_rows`` positions whose logits are needed (the
    embedding lookup is no matrix product), plus attention."""
    held, layers = experts_held(config)
    active = _outside_experts(config) + layers * expert_params(config) * (
        int(config["num_experts_per_tok"]) * held
        / int(config["router_experts"]))
    head = int(config["vocab_size"]) * int(config["hidden_size"])
    return (2.0 * active * tokens + 2.0 * head * logit_rows
            + attention_flops(config, tokens, context_sum))


def train_flops(config: dict, batch: int, seq: int) -> float:
    raise NotImplementedError("this family is served only: no cut of it "
                              "within the sizing floors trains on one chip")


def flash_shape(config: dict, mix: dict) -> tuple:
    raise NotImplementedError("this family has no training mix; its "
                              "prefill kernels are priced by "
                              "prefill_flash_costs")


def prefill_flash_costs(config: dict, length: int) -> list:
    """One prefill's attention kernel calls, a layer each, as
    {"flops", "bytes"}: the FLOPs of the pairs the layer's mask lets
    through (causal, and inside the band in a window layer), q read and
    o written at the query's heads, k and v read once at the layer's
    K/V heads, and the logsumexp row in float32."""
    H = int(config["num_attention_heads"])
    Dqk, Dv = int(config["head_dim"]), int(config["v_head_dim"])
    item = ITEMSIZE[config["torch_dtype"]]
    causal = length * (length + 1) // 2
    costs = []
    for window, _ in _layers(config):
        G = _kv_heads(config, window)
        pairs = window_pairs(config, length, causal) if window else causal
        costs.append({
            "flops": 2.0 * H * (Dqk + Dv) * pairs,
            "bytes": float(length * (H + G) * (Dqk + Dv) * item
                           + 4 * H * length)})
    return costs


def decode_step_bytes(config: dict, rows: int, positions: int,
                      counts: dict) -> float:
    """The bytes one decode step of ``rows`` active rows must move,
    whatever implements it: every weight outside the experts once, the
    head whole and the ``rows`` rows of the embedding; an expert's
    three matrices for each held expert that got a row
    (``counts[EXPERTS_HIT]``, the engine's own count a step; every held
    expert where the engine keeps none); the K and V of the
    ``positions`` attended in the full layers and of ``rows`` windows
    in the window layers (a row whose context is shorter than the
    window has no such cell); and the new token's K and V written in
    every layer."""
    item = ITEMSIZE[config["torch_dtype"]]
    D = int(config["hidden_size"])
    width = int(config["head_dim"]) + int(config["v_head_dim"])
    held, expert_layers = experts_held(config)
    hit = counts.get(EXPERTS_HIT, held * expert_layers)
    weights = (_outside_experts(config) + int(config["vocab_size"]) * D
               + rows * D + hit * expert_params(config))
    kv = 0
    for window, _ in _layers(config):
        attended = (rows * int(config["sliding_window"]) if window
                    else positions)
        kv += (attended + rows) * _kv_heads(config, window) * width
    return float((weights + kv) * item)
