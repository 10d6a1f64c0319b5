"""Finds what a cell is made of, by name. ``BENCHMARK.json`` lists
configurations, cells and metrics; each configuration, traffic mix and
metric is a file of its own under one of the benchmark's ``paths``:

    <file named in the configuration's entry>   the sizes as run
    <path>/workloads/<traffic>.json             a traffic mix (data)
    <path>/metrics/<metric name>.py             read(obs) -> number | None
    <path>/families/<model_type>/               a model family, three files

A configuration's published ``model_type`` names its family, and what
the harness knows of a family is in that directory (``FAMILY_KINDS``):

    reference.py    plain jax.numpy, float32; nothing of ray_tpu:
                    Sizes, sizes_of(config), seeded_params(seed, sz),
                    forward(params, tokens, sz, quant, remat),
                    by_leaf(tree) -> {leaf name: array}
    program.py      the one benchmark file that imports the program's
                    model code: program_config(config, max_seq),
                    make_engine(params, cfg, slots, max_len),
                    prefill_programs(params, cfg, slots, max_len, lengths)
                    -> {length: compiled text}, make_train_step(cfg, mix)
    costs.py        no jax (the driver reads it): n_params(config),
                    forward_flops(config, tokens, context_sum, logit_rows),
                    train_flops(config, batch, seq),
                    flash_shape(config, mix) -> batch, seq, heads,
                    head_dim, itemsize[, K/V heads]; and, for a decode
                    step priced in bytes (a family without the two
                    reads no such share): DECODE_PROGRAM, the step's
                    program as the device trace names it, and
                    decode_step_bytes(config, rows, positions, counts)

A later PR adds files and entries and edits nothing that is there.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
import sys
import types
import zlib
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY_KINDS = ("reference", "program", "costs")


class BenchmarkError(Exception):
    """The run cannot produce a result line; the reason is the message."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["root"] = root
    return bench


def _named(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchmarkError(
        f"no {what} named {name!r}; there are "
        f"{sorted(e['name'] for e in entries)}")


def find_cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def load_config(bench: dict, name: str) -> dict:
    entry = _named(bench["configs"], name, "configuration")
    with open(os.path.join(bench["root"], entry["file"])) as f:
        return json.load(f)


def _find_file(bench: dict, *parts: str) -> str:
    tried = [os.path.join(bench["root"], path, *parts)
             for path in bench["paths"]]
    for candidate in tried:
        if os.path.isfile(candidate):
            return candidate
    raise BenchmarkError(f"none of {tried} exists")


def load_traffic(bench: dict, name: str) -> dict:
    with open(_find_file(bench, "workloads", name + ".json")) as f:
        return json.load(f)


def find_family(bench: dict, config: dict) -> dict:
    """Where the configuration's family lives: the directory
    ``families/<model_type>/`` under one of the benchmark's paths that
    holds all of ``FAMILY_KINDS``. Plain data, found once by the driver
    and handed to the workers and the readers in ``run["family"]``."""
    model_type = config.get("model_type")
    if not model_type:
        raise BenchmarkError(
            "the configuration file states no model_type, so no family")
    where = os.path.dirname(
        _find_file(bench, "families", model_type, "reference.py"))
    missing = [kind + ".py" for kind in FAMILY_KINDS
               if not os.path.isfile(os.path.join(where, kind + ".py"))]
    if missing:
        raise BenchmarkError(f"the family in {where} lacks {missing}")
    return {"model_type": model_type, "dir": where}


def family_module(family: dict, kind: str):
    """One of a family's files as a module. The directory is a package
    of its own, named after the family and where it lies, so a family's
    files may import each other (``from . import reference``) and each
    is loaded once in a process however often it is asked for."""
    package = "bench_family_{}_{:08x}".format(
        re.sub(r"\W", "_", family["model_type"]),
        zlib.crc32(family["dir"].encode()))
    if package not in sys.modules:
        held = sys.modules[package] = types.ModuleType(package)
        held.__path__ = [family["dir"]]
    return importlib.import_module(f"{package}.{kind}")


def load_reader(bench: dict, name: str) -> Callable[[dict], object]:
    """The metric's own reader: ``read(obs)`` from ``metrics/<name>.py``.
    It returns None where it finds nothing to read."""
    path = _find_file(bench, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries this cell reports in this mode. One that lists
    ``workloads`` is read only there; a per-layer metric that lists none
    is read wherever the end-to-end metric it moves is reported."""
    def applies(metric: dict) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    if not trace:
        return end_to_end
    reported = {m["name"] for m in end_to_end}
    return [m for m in bench["per_layer"]
            if applies(m) and m["moves"] in reported]


def read_metrics(bench: dict, cell: str, trace: bool,
                 obs: dict) -> Dict[str, dict]:
    """name -> {"value", "unit"} for every metric whose reader found
    something to read."""
    out = {}
    for metric in cell_metrics(bench, cell, trace):
        value = load_reader(bench, metric["name"])(obs)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out
