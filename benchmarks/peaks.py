"""The chip's peaks and the arithmetic of a kernel's operations and
bytes: what the algorithm needs, computed from shapes, never read from
the program. A model's own counts (parameters, a forward's and a train
step's FLOPs, the shape its kernels are priced at) are its family's:
``families/<model_type>/costs.py``.

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


def flash_fwd_cost(batch: int, seq: int, heads: int, head_dim: int,
                   itemsize: int = 2, kv_heads: int = None) -> dict:
    """One causal flash-attention forward call [B, T, H, Dh]: the FLOPs
    of the unmasked pairs, and the bytes of q read and o written once
    at the query's ``heads``, of k and v read once at ``kv_heads``
    (None: the query's), and the logsumexp row in float32."""
    kv_heads = heads if kv_heads is None else kv_heads
    pairs = batch * heads * seq * (seq + 1) // 2
    flops = 4.0 * head_dim * pairs
    bytes_ = 2.0 * batch * seq * (heads + kv_heads) * head_dim * itemsize \
        + 4.0 * batch * heads * seq
    return {"flops": flops, "bytes": bytes_}


def flash_bwd_cost(batch: int, seq: int, heads: int, head_dim: int,
                   itemsize: int = 2, kv_heads: int = None) -> dict:
    """The two backward kernels together (dK/dV and dQ), by the usual
    accounting: five matrix products of the forward's size are needed
    (S recomputed once, dV, dP, dK, dQ), 2.5 times the forward. That
    each kernel recomputes S and dP for itself is the implementation's
    cost, not the algorithm's, and is not counted. Bytes: q, o, do read
    and dq written once at the query's ``heads``; k, v read and dk, dv
    written once at ``kv_heads`` (None: the query's)."""
    kv_heads = heads if kv_heads is None else kv_heads
    pairs = batch * heads * seq * (seq + 1) // 2
    flops = 2.0 * head_dim * pairs * 5
    bytes_ = 4.0 * batch * seq * (heads + kv_heads) * head_dim * itemsize \
        + 8.0 * batch * heads * seq
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """The least time the chip could take, and which bound sets it."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
