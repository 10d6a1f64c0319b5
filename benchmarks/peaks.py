"""The chip's peaks and the arithmetic of operations and bytes: what the
algorithm needs, computed from shapes, never read from the program.

Peaks are keyed by jax's exact ``device_kind``. Source: Google Cloud
documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
16 GB of HBM at 819 GB/s per chip. A kind that is not listed is an
error, never a default. (Copied from ``ray_tpu/models/bench_model.py``
``PEAK_BF16_TFLOPS``, with the memory peaks added.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device_kind {device_kind!r}; add it "
            f"to benchmarks/peaks.py with its source") from None


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


def flash_fwd_cost(batch: int, seq: int, heads: int, head_dim: int,
                   itemsize: int = 2) -> dict:
    """One causal flash-attention forward call [B, T, H, Dh]: the FLOPs
    of the unmasked pairs, and the bytes of q, k, v read and o written
    once (the logsumexp row too, in float32)."""
    pairs = batch * heads * seq * (seq + 1) // 2
    flops = 4.0 * head_dim * pairs
    bytes_ = 4.0 * batch * seq * heads * head_dim * itemsize \
        + 4.0 * batch * heads * seq
    return {"flops": flops, "bytes": bytes_}


def flash_bwd_cost(batch: int, seq: int, heads: int, head_dim: int,
                   itemsize: int = 2) -> dict:
    """The two backward kernels together (dK/dV and dQ), by the usual
    accounting: five matrix products of the forward's size are needed
    (S recomputed once, dV, dP, dK, dQ), 2.5 times the forward. That
    each kernel recomputes S and dP for itself is the implementation's
    cost, not the algorithm's, and is not counted. Bytes: q, k, v, o,
    do read and dq, dk, dv written once."""
    pairs = batch * heads * seq * (seq + 1) // 2
    flops = 2.0 * head_dim * pairs * 5
    bytes_ = 8.0 * batch * seq * heads * head_dim * itemsize \
        + 8.0 * batch * heads * seq
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """The least time the chip could take, and which bound sets it."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
