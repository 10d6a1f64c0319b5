#!/usr/bin/env python3
"""Legs on real chips that chip_smoke.py's one-chip contract leaves out.

    python3 ci/chip_legs.py        (from the repo root, through the chip tool)

Which legs run follows from how many chips jax finds:

* four chips — (a) ``Trainer(num_workers=1, resources_per_worker=
  {"TPU": 4})``: the worker builds ``parallel.build_mesh`` over its four
  devices and steps the 168M model through ``make_train_step(...,
  mesh=...)`` on (dp2, tp2) and again on (pp2, sp2), so Megatron psum,
  ring attention's ppermute and the GPipe scan each run on real ICI;
  (b) four ``num_tpus=1`` replicas of chip_smoke's deployment behind one
  proxy, each on a chip of its own;
* one chip — (c) a second ``num_tpus=1`` actor stays pending while the
  first holds the chip, and gets the chip once the first is killed.

Same rules as chip_smoke.py: the driver never imports jax, every device
fact comes from the worker that holds ``TPU``, any failure exits
non-zero. One JSON line per leg.
"""

from __future__ import annotations

import json
import math
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import cloudpickle  # noqa: E402

import chip_smoke  # noqa: E402
from chip_smoke import check  # noqa: E402

# workers unpickle chip_smoke's code by value: they need not import it
cloudpickle.register_pickle_by_value(chip_smoke)

MESHES = ({"dp": 2, "tp": 2}, {"pp": 2, "sp": 2})


def mesh_train_func(config: dict) -> dict:
    """Inside the Trainer's worker, which holds all four chips."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.models import (DENSE_168M, ParallelConfig,
                                init_train_state, make_train_step)
    from ray_tpu.parallel import MeshConfig, build_mesh

    cfg, (B, T) = DENSE_168M, (16, 1024)
    devices = jax.devices()
    tokens = jax.random.randint(jax.random.key(1), (B, T + 1), 0,
                                cfg.vocab)
    result = {"device": chip_smoke.device_report(), "meshes": []}
    for sizes in config["meshes"]:
        mesh = build_mesh(MeshConfig(**sizes), devices)
        pcfg = ParallelConfig(remat=True, **{ax: ax for ax in sizes})
        optimizer = optax.adamw(3e-4)
        step, _ = make_train_step(cfg, pcfg, mesh=mesh,
                                  optimizer=optimizer)
        params, opt_state = init_train_state(
            jax.random.key(0), cfg, pcfg, mesh, optimizer)
        batch = jax.device_put(
            {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]},
            NamedSharding(mesh, P(pcfg.dp, pcfg.sp)))
        losses = []
        for i in range(config["steps"]):
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
            train.report(mesh=sizes, step=i, loss=losses[-1])
        result["meshes"].append({
            "mesh": sizes, "losses": losses,
            "bytes_in_use": [d.memory_stats()["bytes_in_use"]
                             for d in devices]})
        del params, opt_state, batch, step
    return result


def leg_mesh_train() -> dict:
    from ray_tpu import train

    trainer = train.Trainer(num_workers=1,
                            resources_per_worker={"TPU": 4})
    try:
        result = trainer.run(mesh_train_func,
                             {"meshes": MESHES, "steps": 3})[0]
    finally:
        trainer.shutdown()
    dev = result["device"]
    check(dev["platform"] == "tpu" and dev["device_count"] == 4
          and dev["chips"] == "0,1,2,3", f"not four bound chips: {dev}")
    for row in result["meshes"]:
        losses = row["losses"]
        check(all(math.isfinite(x) for x in losses)
              and losses[-1] < losses[0],
              f"loss not finite and falling on {row['mesh']}: {losses}")
        check(all(b > 0 for b in row["bytes_in_use"]),
              f"a device holds nothing on {row['mesh']}: {row}")
    return {"leg": "a: mesh train on four chips", **result}


def leg_four_replicas() -> dict:
    from ray_tpu import serve

    rng = random.Random(0)
    serve.start()
    try:
        serve.deployment(
            chip_smoke.SmokeLM, name="lm", num_replicas=4,
            ray_actor_options={"num_tpus": 1}).deploy(None, 8, 1024)
        url = f"http://{serve.get_http_address()}/lm"
        # the proxy deals requests round-robin: eight, one at a time,
        # reach every replica twice
        for _ in range(8):
            ans = chip_smoke.http_json(url, {
                "prompt": chip_smoke.seeded_prompt(rng, 128, 32768),
                "max_tokens": 8})
            check(len(ans["tokens"]) == 8, f"short answer: {ans}")
        replicas = {}
        for _ in range(8):
            seen = chip_smoke.http_json(url + "/stats")
            replicas[seen["device"]["pid"]] = seen
    finally:
        serve.shutdown()
    check(len(replicas) == 4, f"{len(replicas)} replicas answered, not 4")
    devs = [r["device"] for r in replicas.values()]
    check(sorted(d["chips"] for d in devs) == ["0", "1", "2", "3"],
          f"replicas do not hold four different chips: {devs}")
    check(all(d["platform"] == "tpu" and d["device_count"] == 1
              for d in devs), f"a replica sees more than its chip: {devs}")
    check(all(r["decode"]["completed"] > 0 for r in replicas.values()),
          f"a replica answered nothing: {replicas}")
    return {"leg": "b: four one-chip replicas",
            "replicas": list(replicas.values())}


def leg_second_actor_waits() -> dict:
    import ray_tpu

    @ray_tpu.remote(num_tpus=1)
    class Holder:
        def work(self):
            import jax.numpy as jnp

            x = jnp.ones((512, 512), jnp.bfloat16)
            return {**chip_smoke.device_report(),
                    "sum": float((x @ x).sum())}

    first, second = Holder.remote(), Holder.remote()
    one = ray_tpu.get(first.work.remote(), timeout=120)
    waiting = second.work.remote()
    ready, _ = ray_tpu.wait([waiting], timeout=15)
    check(not ready, "a second actor got the only chip")
    still = ray_tpu.get(first.work.remote(), timeout=60)
    check(still["pid"] == one["pid"] and still["sum"] == one["sum"],
          f"the first actor did not survive: {one} then {still}")
    ray_tpu.kill(first)
    two = ray_tpu.get(waiting, timeout=120)
    check(two["platform"] == "tpu" and two["pid"] != one["pid"]
          and two["chips"] == one["chips"],
          f"the chip was not handed on after the kill: {one} then {two}")
    ray_tpu.kill(second)
    return {"leg": "c: second actor waits for the one chip",
            "first": one, "second": two}


def main() -> int:
    device = chip_smoke.probe_device()
    check(device["count"] in (1, 4),
          f"legs exist for one chip or four, found {device}")
    legs = [leg_second_actor_waits] if device["count"] == 1 else \
        [leg_mesh_train, leg_four_replicas]
    with chip_smoke.session(device["count"]):
        for leg in legs:
            print(json.dumps(leg()), flush=True)
        check("jax" not in sys.modules, "the driver imported jax")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    chip_smoke.run_as_script(main)
