"""Single-process device script: the 168M train step, the flash
attention kernels and KV-cached decode on ONE chip, printed as one
JSON line.

Run as ``python -m ray_tpu.models.bench_model`` in a process of its
own: it takes the chip, so no ray_tpu session that leases ``TPU`` may
be running beside it. It needs a TPU whose ``device_kind`` is in
``PEAK_BF16_TFLOPS`` and raises otherwise; no phase failure is
caught. The system's main path (serve and train through ray_tpu's
front door) is ``chip_smoke.py``; this script isolates the device
programs from the runtime around them.

Timing is host clock around ``block_until_ready``, after one warm-up
call that pays the compile. FLOP accounting (the 6ND convention plus
the exact attention term):
  dense train FLOPs/step = 6 * n_params * tokens
  attention FLOPs/step   = 12 * L * B * H * T^2 * Dh  (x1/2 causal)
MFU = (dense + attention) / step_time / peak.
"""

from __future__ import annotations

import json
import time

# bf16 peak TFLOP/s of one chip, by exact ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16 per chip). A kind that is not listed is an error.
PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
}


def peak_tflops(device_kind: str) -> float:
    try:
        return PEAK_BF16_TFLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; "
            f"add it to PEAK_BF16_TFLOPS with its source") from None


def _timed(fn, reps: int) -> float:
    """Mean seconds per call of ``fn`` (which returns jax arrays)."""
    import jax

    jax.block_until_ready(fn())  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _train_row(cfg, B: int, T: int, peak: float, steps: int) -> dict:
    import jax
    import numpy as np

    from ray_tpu.models import transformer as tfm

    params = jax.jit(tfm.init_params, static_argnames="cfg")(
        jax.random.key(0), cfg=cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    step_fn, optimizer = tfm.make_train_step(
        cfg, tfm.ParallelConfig(remat=True))
    state = [params, optimizer.init(params)]
    tokens = jax.random.randint(jax.random.key(1), (B, T + 1), 0, cfg.vocab)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    def one_step():
        state[0], state[1], loss = step_fn(state[0], state[1], batch)
        return loss

    dt = _timed(one_step, steps)
    flops = 6.0 * n_params * B * T + (
        12.0 * cfg.n_layers * B * cfg.n_heads * T * T * cfg.head_dim) / 2.0
    return {"n_params": n_params, "batch": B, "seq": T, "remat": "full",
            "step_ms": round(dt * 1e3, 2),
            "tokens_per_s": round(B * T / dt, 1),
            "achieved_tflops": round(flops / dt / 1e12, 2),
            "peak_tflops": peak,
            "mfu": round(flops / dt / 1e12 / peak, 4)}


def _flash_row(shape, reps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention, flash_attention

    B, T, H, D = shape
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
               for kk in jax.random.split(jax.random.key(2), 3))

    def fwd_bwd(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    t = {}
    for name, fn in (("flash", flash_attention), ("xla", attention)):
        fwd, both = jax.jit(fn), fwd_bwd(fn)
        t[name + "_fwd"] = _timed(lambda: fwd(q, k, v), reps)
        t[name + "_fwdbwd"] = _timed(lambda: both(q, k, v), reps)
    fwd_flops = 4.0 * B * H * T * T * D / 2.0
    return {"shape": list(shape),
            "fwd_ms": round(t["flash_fwd"] * 1e3, 2),
            "fwd_tflops": round(fwd_flops / t["flash_fwd"] / 1e12, 2),
            "xla_fwd_ms": round(t["xla_fwd"] * 1e3, 2),
            "fwdbwd_ms": round(t["flash_fwdbwd"] * 1e3, 2),
            "xla_fwdbwd_ms": round(t["xla_fwdbwd"] * 1e3, 2)}


def _decode_row(cfg, B: int, T0: int, steps: int) -> dict:
    import jax

    from ray_tpu.models import decode as dec
    from ray_tpu.models import transformer as tfm

    params = jax.jit(tfm.init_params, static_argnames="cfg")(
        jax.random.key(3), cfg=cfg)
    prompt = jax.random.randint(jax.random.key(4), (B, T0), 0, cfg.vocab)

    def gen(n):
        return dec.generate(params, prompt, cfg, steps=n,
                            max_len=T0 + steps + 1)

    # prefill + n decode steps, less prefill + 1: the per-step time
    dt = (_timed(lambda: gen(steps), 3) - _timed(lambda: gen(1), 3)) \
        / (steps - 1)
    return {"batch": B, "prompt_len": T0, "steps": steps,
            "per_token_ms": round(dt * 1e3, 3),
            "tokens_per_s": round(B / dt, 1)}


def run() -> dict:
    import jax

    from ray_tpu.models.transformer import DENSE_168M

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench_model measures a TPU; jax's default device is "
            f"{dev.platform!r} ({dev.device_kind})")
    peak = peak_tflops(dev.device_kind)
    return {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "train": _train_row(DENSE_168M, 16, 1024, peak, steps=8),
        "flash_attention": _flash_row((4, 4096, 8, 128), reps=8),
        "decode": _decode_row(DENSE_168M, 16, 512, steps=64),
    }


if __name__ == "__main__":
    from ray_tpu._private import compile_cache

    compile_cache.export()
    print(json.dumps(run()))
