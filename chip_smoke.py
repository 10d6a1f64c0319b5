#!/usr/bin/env python3
"""The quickest proof that ray_tpu still starts on the chip.

    python3 chip_smoke.py

drives the system's main path once, through the entry points a user
calls, at the full width of the 168M dense model
(``ray_tpu.models.DENSE_168M``; weights random from a seed), in ONE
ray_tpu session whose driver never imports jax:

* serve leg — ``ray_tpu.init(num_tpus=n)``, ``serve.start()``, a
  deployment with ``ray_actor_options={"num_tpus": 1}`` that builds
  seeded params, a ``JaxSlotEngine`` and a ``serve.DecodeScheduler``;
  concurrent HTTP POSTs through the proxy's real socket, one of them
  admitted while the batch is decoding;
* train leg — after ``serve.shutdown()``, so the chip changes hands:
  ``train.Trainer(num_workers=1, use_tpu=True).run(train_func)`` takes
  optimizer steps of the remat train step at B16 x T1024 on one fixed
  seeded batch and ``report()``s each loss;
* kernel leg — inside the trainer's worker: ``flash_attention``
  compiled by Mosaic (``interpret=False``), forward and both backward
  kernels, against ``ops.attention.attention`` at head_dim 64 and 128;
  and ``decode_attention``'s kernel against its XLA form at the decode
  shapes of the four cells whose decode step attends (16 heads of 128
  over 8 x 1024; 64 on 4 at 192 / 128 over 128 x 3200; 20 on 1 at 128
  over 256 x 2048; 40 on 10 at 128 over 64 x 6144), slots at unlike
  positions, the cache poisoned past them, with the chunk each took.

Every device fact printed comes from inside the worker that holds
``TPU``. It exits non-zero with the reason on any failure — at once
when jax finds no TPU — and on success prints as its last line

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}

A chip belongs to one process at a time: nothing else that needs the
chip may run beside this script.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import random
import subprocess
import sys
import threading
import time
import urllib.request

# bf16 flash kernel vs the XLA reference on the same bf16 inputs: the
# largest difference, as a share of the reference's largest magnitude.
# Both round q/k/v products through bf16 (8 mantissa bits, 2^-8 = 0.4%);
# the gradients pass through two such roundings.
BF16_TOL = {"fwd": 2e-2, "bwd": 4e-2}
KERNEL_SHAPES = ((2, 1024, 16, 64), (4, 4096, 8, 128))
# decode steps: slots, query heads, q.k width, value width, K/V heads,
# rows a slot (ouro-2.6b.decode-closed; mimo-v2-flash-ep16-d7.reason-closed)
# B, H, D, Dv, G, rows of the four cells whose decode step attends
DECODE_SHAPES = ((8, 16, 128, 128, 16, 1024), (128, 64, 192, 128, 4, 3200),
                 (256, 20, 128, 128, 1, 2048), (64, 40, 128, 128, 10, 6144))
MOSAIC_CALL = "tpu_custom_call"


class SmokeFailure(Exception):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ------------------------------------------------------------------
# Code that runs inside the worker that holds TPU
# ------------------------------------------------------------------

def device_report() -> dict:
    """What jax and the raylet's binding look like from this process."""
    import jax

    devs = jax.devices()
    env = os.environ
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs), "pid": os.getpid(),
            "chips": env.get("RAY_TPU_CHIPS"),
            "tpu_visible_chips": env.get("TPU_VISIBLE_CHIPS"),
            "jax_platforms": env.get("JAX_PLATFORMS"),
            "compile_cache_dir": env.get("JAX_COMPILATION_CACHE_DIR")}


def make_config(spec):
    """None -> the 168M config; else TransformerConfig keyword
    arguments, with ``dtype`` by name (the driver cannot name a jax
    dtype: it never imports jax)."""
    import jax.numpy as jnp

    from ray_tpu.models import DENSE_168M, TransformerConfig

    if spec is None:
        return DENSE_168M
    spec = dict(spec)
    spec["dtype"] = jnp.dtype(spec["dtype"]).type
    return TransformerConfig(**spec)


def seeded_params(cfg, seed: int):
    import jax

    from ray_tpu.models import init_params

    return jax.jit(init_params, static_argnames="cfg")(
        jax.random.key(seed), cfg=cfg)


class SmokeLM:
    """The smoke's deployment: POST {"prompt": [...], "max_tokens": n}
    -> {"tokens": [...]}; GET <route>/stats and
    <route>/programs?lengths=a,b report from inside the replica."""

    def __init__(self, cfg_spec=None, slots: int = 8, max_len: int = 1024):
        from ray_tpu import serve

        self.cfg = make_config(cfg_spec)
        self.slots, self.max_len = slots, max_len
        self.params = seeded_params(self.cfg, seed=0)
        self.decode_scheduler = serve.DecodeScheduler(serve.JaxSlotEngine(
            self.params, self.cfg, slots=slots, max_len=max_len))

    async def __call__(self, request):
        if request.method == "GET":
            if request.path.endswith("/programs"):
                return self.programs(
                    int(n) for n in request.query["lengths"].split(","))
            return {"device": device_report(),
                    "decode": self.decode_scheduler.stats()}
        body = request.json()
        tokens = await self.decode_scheduler.submit(
            body["prompt"], max_tokens=int(body["max_tokens"]))
        return {"tokens": tokens}

    def programs(self, lengths) -> dict:
        """Mosaic custom calls in the compiled prefill program, per
        prompt length (the same jit the engine calls; a persistent-
        cache hit after traffic)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import decode

        cache = jax.eval_shape(
            lambda: decode.init_slot_cache(self.cfg, self.slots,
                                           self.max_len))
        out = {}
        for length in lengths:
            text = decode.slot_prefill.lower(
                self.params, jax.ShapeDtypeStruct((1, length), jnp.int32),
                cache, jnp.int32(0), self.cfg).compile().as_text()
            out[str(length)] = text.count(MOSAIC_CALL)
        return {"prefill_mosaic_calls": out}


def kernel_checks(shapes, dtype: str, interpret: bool) -> list:
    """flash_attention forward and backward against the XLA reference,
    each compiled once; returns one row of normalized errors and Mosaic
    call counts per shape."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention, flash_attention

    def flash(q, k, v):
        return flash_attention(q, k, v, interpret=interpret)

    def err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    rows = []
    for shape in shapes:
        q, k, v, w = (jax.random.normal(kk, shape, jnp.dtype(dtype))
                      for kk in jax.random.split(jax.random.key(7), 4))

        def grads(fn):
            # weighted sum: a plain sum has dO = 1 and hides a wrong
            # delta = rowsum(dO * O) term
            return jax.grad(
                lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                 * w.astype(jnp.float32)).sum(),
                argnums=(0, 1, 2))

        t0 = time.perf_counter()
        fwd = jax.jit(flash).lower(q, k, v).compile()
        bwd = jax.jit(grads(flash)).lower(q, k, v).compile()
        compile_s = time.perf_counter() - t0
        out, ref = fwd(q, k, v), jax.jit(attention)(q, k, v)
        got, want = bwd(q, k, v), jax.jit(grads(attention))(q, k, v)
        row = {"shape": list(shape), "dtype": dtype,
               "interpret": interpret,
               "compile_s": round(compile_s, 2),
               "fwd_mosaic_calls": fwd.as_text().count(MOSAIC_CALL),
               "bwd_mosaic_calls": bwd.as_text().count(MOSAIC_CALL),
               "fwd_err": err(out, ref),
               "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all())}
        for name, g, r in zip(("dq", "dk", "dv"), got, want):
            row[name + "_err"] = err(g, r)
        rows.append(row)
    return rows


def decode_kernel_checks(shapes, dtype: str, interpret: bool) -> list:
    """``decode_attention`` through its kernel against its XLA form on
    a clean copy, layer 1 of a run of 2, slots at unlike positions
    (the first, the last and a spread between) and NaN past each: one
    row per shape of the normalized error, the Mosaic call count and
    the chunk of positions the shape was given."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops.attention import (cached_attention, decode_attention,
                                       decode_rows_fetched)

    rows_out = []
    for B, H, D, Dv, G, rows in shapes:
        keys = jax.random.split(jax.random.key(9), 3)
        row = (lambda w: (H, w)) if G == H else (lambda w: (G * w,))
        q = jax.random.normal(keys[0], (B, H, D), jnp.dtype(dtype))
        k = jax.random.normal(keys[1], (2, B, rows) + row(D), q.dtype)
        v = jax.random.normal(keys[2], (2, B, rows) + row(Dv), q.dtype)
        pos = (jnp.arange(B) * (rows - 1) // max(B - 1, 1)).astype(jnp.int32)
        past = (jnp.arange(rows)[None, :] > pos[:, None]).reshape(
            (1, B, rows) + (1,) * (k.ndim - 3))
        valid = jnp.arange(rows)[None, None, :] <= pos[:, None, None]

        def kernel(q, k, v, pos):
            return decode_attention(q, k, v, jnp.int32(1), pos,
                                    interpret=interpret)

        def xla(q, k, v, pos):
            return cached_attention(
                q, lax.dynamic_index_in_dim(k, 1, keepdims=False),
                lax.dynamic_index_in_dim(v, 1, keepdims=False), valid,
                D ** -0.5)

        compiled = jax.jit(kernel).lower(q, k, v, pos).compile()
        poisoned = [jnp.where(past, jnp.nan, t) for t in (k, v)]
        got = compiled(q, *poisoned, pos).astype(jnp.float32)
        del poisoned                    # 4 GB at the last shape
        clean = [jnp.where(past, 0, t) for t in (k, v)]
        del k, v
        want = jax.jit(xla)(q, *clean, pos).astype(jnp.float32)
        rows_out.append({
            "shape": [B, H, D, Dv, G, rows], "dtype": dtype,
            "interpret": interpret,
            "chunk": decode_rows_fetched(q, *clean, interpret=interpret),
            "mosaic_calls": compiled.as_text().count(MOSAIC_CALL),
            "err": float(jnp.max(jnp.abs(got - want))
                         / jnp.max(jnp.abs(want))),
            "finite": bool(jnp.isfinite(got).all())})
    return rows_out


def train_func(config: dict) -> dict:
    """The train leg, inside the Trainer's worker: the kernel checks,
    then ``steps`` optimizer steps on one fixed seeded batch."""
    import jax

    from ray_tpu import train
    from ray_tpu.models import ParallelConfig, make_train_step

    result = {"device": device_report()}
    result["kernels"] = kernel_checks(
        config["kernel_shapes"], config["kernel_dtype"],
        config["interpret"])
    result["decode_kernels"] = decode_kernel_checks(
        config["decode_shapes"], config["kernel_dtype"],
        config["interpret"])

    cfg = make_config(config.get("cfg"))
    B, T = config["batch"], config["seq"]
    step, optimizer = make_train_step(cfg, ParallelConfig(remat=True))
    params = seeded_params(cfg, seed=0)
    opt_state = jax.jit(optimizer.init)(params)
    tokens = jax.random.randint(jax.random.key(1), (B, T + 1), 0,
                                cfg.vocab)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch).compile()
    result["compile_s"] = round(time.perf_counter() - t0, 2)
    result["train_step_mosaic_calls"] = \
        compiled.as_text().count(MOSAIC_CALL)
    losses = []
    for i in range(config["steps"]):
        params, opt_state, loss = compiled(params, opt_state, batch)
        losses.append(float(loss))
        train.report(step=i, loss=losses[-1])
    result["losses"] = losses
    return result


# ------------------------------------------------------------------
# The driver: stays off jax
# ------------------------------------------------------------------

_PROBE = ("import json, jax; d = jax.devices(); print(json.dumps("
          "{'platform': d[0].platform, 'kind': d[0].device_kind, "
          "'count': len(d)}))")


def probe_device() -> dict:
    """What jax finds, asked of a short-lived child that has exited —
    and let go of the chip — before the session starts."""
    r = subprocess.run([sys.executable, "-c", _PROBE], text=True,
                       capture_output=True, timeout=300)
    check(r.returncode == 0,
          f"jax could not start in a child process:\n{r.stderr[-2000:]}")
    device = json.loads(r.stdout.strip().splitlines()[-1])
    check(device["platform"] == "tpu",
          f"no TPU chip: jax's default platform is "
          f"{device['platform']!r} ({device['kind']}, JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS')!r})")
    return device


def http_json(url: str, body=None, timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    with urllib.request.urlopen(
            urllib.request.Request(url, data=data), timeout=timeout) as r:
        return json.loads(r.read())


def seeded_prompt(rng: random.Random, length: int, vocab: int) -> list:
    return [rng.randrange(vocab) for _ in range(length)]


def generate_all(url: str, requests: list) -> list:
    """POST every (prompt, max_tokens) concurrently; answers in order."""
    answers = [None] * len(requests)

    def one(i):
        prompt, n = requests[i]
        try:
            answers[i] = http_json(url, {"prompt": prompt,
                                         "max_tokens": n})
        except Exception as e:  # noqa: BLE001 — reported by the caller
            answers[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return answers


def serve_leg(vocab: int, cfg_spec=None, lengths=(128, 256),
              max_len: int = 1024, tokens=(32, 64)) -> dict:
    from ray_tpu import serve

    rng = random.Random(0)
    t_leg = time.perf_counter()
    serve.start()
    try:
        serve.deployment(
            SmokeLM, name="lm",
            ray_actor_options={"num_tpus": 1}).deploy(
                cfg_spec, 8, max_len)
        url = f"http://{serve.get_http_address()}/lm"
        device = http_json(url + "/stats")["device"]

        # One request per prompt length first: they pay the compiles
        # (prefill per length, the decode step).
        t0 = time.perf_counter()
        for length in lengths:
            generate_all(url, [(seeded_prompt(rng, length, vocab), 2)])
        warmup_s = time.perf_counter() - t0

        # The batch: four concurrent requests, and a fifth sent once
        # the replica reports decode steps under way.
        first = [(seeded_prompt(rng, lengths[i % 2], vocab),
                  tokens[1]) for i in range(4)]
        late = (seeded_prompt(rng, lengths[0], vocab), tokens[0])
        seen = http_json(url + "/stats")["decode"]
        steps_before = seen["steps"]
        answers = [None]
        wave = threading.Thread(
            target=lambda: answers.__setitem__(0, generate_all(url, first)))
        wave.start()
        while wave.is_alive():
            seen = http_json(url + "/stats")["decode"]
            if seen["steps"] > steps_before and seen["active_slots"]:
                break
            time.sleep(0.002)
        late_answer = generate_all(url, [late])[0]
        wave.join()
        stats = http_json(url + "/stats")["decode"]
        programs = http_json(
            f"{url}/programs?lengths={','.join(map(str, lengths))}")
    finally:
        serve.shutdown()

    counts = []
    for (prompt, n), ans in zip(first + [late], answers[0] + [late_answer]):
        check(isinstance(ans, dict),
              f"request failed (shed or errored): {ans!r}")
        toks = ans["tokens"]
        check(len(toks) == n, f"asked {n} tokens, got {len(toks)}")
        check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
              f"token outside the vocabulary: {toks}")
        counts.append(len(toks))
    check(stats["shed"] == 0, f"requests were shed: {stats}")
    check(stats["admitted_mid_batch"] > 0,
          f"no request was admitted mid-batch: {stats}")
    return {"leg": "serve", "device": device, "token_counts": counts,
            "late_request_sent_at": {"steps": seen["steps"],
                                     "active_slots": seen["active_slots"]},
            "decode": stats, "warmup_s": round(warmup_s, 2),
            "wall_s": round(time.perf_counter() - t_leg, 2), **programs}


def train_leg(config: dict) -> dict:
    from ray_tpu import train

    reported = []

    class Collect(train.TrainingCallback):
        def handle_result(self, results, **info):
            reported.extend(r["loss"] for r in results)

    t_leg = time.perf_counter()
    trainer = train.Trainer(num_workers=1, use_tpu=True)
    try:
        result = trainer.run(train_func, config,
                             callbacks=[Collect()])[0]
    finally:
        trainer.shutdown()
    losses = result["losses"]
    check(reported == losses,
          f"report() delivered {reported}, the worker saw {losses}")
    check(all(math.isfinite(x) for x in losses)
          and losses[-1] < losses[0],
          f"loss not finite and falling: {losses}")
    return {"leg": "train", "wall_s": round(time.perf_counter() - t_leg, 2),
            **result}


def check_on_chip(leg: dict, tol: dict) -> None:
    """The checks that only hold on the TPU (the CPU test skips them)."""
    dev = leg["device"]
    check(dev["platform"] == "tpu" and dev["chips"] is not None,
          f"the {leg['leg']} leg's worker is not on a bound TPU: {dev}")
    if leg["leg"] == "serve":
        check(all(n > 0 for n in leg["prefill_mosaic_calls"].values()),
              f"compiled prefill has no Mosaic call: {leg}")
        return
    check(leg["train_step_mosaic_calls"] > 0,
          "compiled train step has no Mosaic call")
    for row in leg["kernels"]:
        check(row["finite"] and row["fwd_mosaic_calls"] == 1
              and row["bwd_mosaic_calls"] == 3,
              f"flash kernels did not all compile with Mosaic: {row}")
        check(row["fwd_err"] <= tol["fwd"] and all(
            row[g + "_err"] <= tol["bwd"] for g in ("dq", "dk", "dv")),
            f"flash attention disagrees with the reference: {row}")
    for row in leg["decode_kernels"]:
        check(row["mosaic_calls"] == 1 and row["chunk"] < row["shape"][-1],
              f"the decode kernel did not compile with Mosaic: {row}")
        check(row["finite"] and row["err"] <= tol["fwd"],
              f"the decode kernel disagrees with its XLA form: {row}")


def dump_worker_logs(session_dir: str, tail: int = 3000) -> None:
    log_dir = os.path.join(session_dir, "logs")
    for name in sorted(n for n in os.listdir(log_dir)
                       if n.endswith(".log")):
        with open(os.path.join(log_dir, name), "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - tail))
            text = f.read().decode(errors="replace").strip()
        if text:
            print(f"--- {name}\n{text}", file=sys.stderr)


@contextlib.contextmanager
def session(num_tpus: int):
    """One ray_tpu session whose workers' log tails go to stderr when
    the body fails, and whose processes are stopped either way."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, num_tpus=num_tpus, log_to_driver=False)
    try:
        yield info
    except BaseException:
        dump_worker_logs(info["session_dir"])
        raise
    finally:
        ray_tpu.shutdown()


def run_as_script(main) -> None:
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        sys.exit(1)


def main() -> int:
    import faulthandler

    faulthandler.dump_traceback_later(1150, exit=True)
    from ray_tpu._private import native

    t_start = time.perf_counter()
    device = probe_device()
    with session(device["count"]):
        served = serve_leg(vocab=32768)
        check_on_chip(served, BF16_TOL)
        print(json.dumps(served), flush=True)
        trained = train_leg({
            "cfg": None, "batch": 16, "seq": 1024, "steps": 5,
            "kernel_shapes": KERNEL_SHAPES, "decode_shapes": DECODE_SHAPES,
            "kernel_dtype": "bfloat16",
            "interpret": False})
        check_on_chip(trained, BF16_TOL)
        print(json.dumps(trained), flush=True)
        check(served["device"]["pid"] != trained["device"]["pid"],
              "the chip did not change hands between the legs")
        check("jax" not in sys.modules, "the driver imported jax")
        print(json.dumps({
            "host": {"native_fastpath_built":
                     native.loaded_fastpath() is not None,
                     "compile_cache_dir":
                     trained["device"]["compile_cache_dir"],
                     "driver_imported_jax": "jax" in sys.modules},
            "compile_s": {"serve_warmup": served["warmup_s"],
                          "train_step": trained["compile_s"],
                          "kernels": sum(r["compile_s"]
                                         for r in trained["kernels"])},
            "wall_s": round(time.perf_counter() - t_start, 2)}),
            flush=True)
    for leg in (served, trained):
        seen = leg["device"]
        check((seen["platform"], seen["device_kind"]) ==
              (device["platform"], device["kind"]),
              f"the worker saw {seen}, the probe saw {device}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    run_as_script(main)
