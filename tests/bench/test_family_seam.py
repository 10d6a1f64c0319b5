"""The seam between the harness and a model family. Which family a
configuration belongs to is its published ``model_type``; what the
harness knows of a family is three files found by that name
(``loader.find_family``). A second family, ``tests/bench/toy/``, comes
as files and entries alone and is served through the same front door;
the ``ouro`` family's weights are still the parent commit's, bit for
bit; a ``model_type`` with no files gives no result; and no shared
module of the benchmark knows a block.
"""

import ast
import copy
import glob
import hashlib
import json
import os
import shutil
import sys

import cloudpickle
import pytest
from test_bench_run import (MIXES, TINY, AlteredTokenLM,  # noqa: F401
                            TracedOnCpuLM, build_tiny_bench, check_line,
                            compile_cache, cpu_tpu_workers)
from test_bench_units import (every_cell_reports_what_the_contract_asks,
                              keeps_the_contract)

from benchmarks import loader, peaks, run

cloudpickle.register_pickle_by_value(sys.modules[__name__])

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.join(loader.ROOT, "benchmarks")
TOY = {"model_type": "toy", "hidden_size": 32, "norm_eps": 1e-6,
       "tie_word_embeddings": True, "vocab_size": 128}


# --------------------------------------------- a second family, as files

def build_toy_bench(root: str, real: dict) -> dict:
    """A benchmark root of its own. The shared harness is the repo's
    (``benchmarks`` on the path, its metric readers copied); the toy
    family, its configuration, its mix and its cell are new files under
    a new path and new entries beside whatever ``real`` holds."""
    shutil.copytree(os.path.join(BENCHMARKS, "metrics"),
                    os.path.join(root, "benchmarks", "metrics"))
    shutil.copytree(os.path.join(HERE, "toy"), os.path.join(root, "toy"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub, name, data in (("configs", "toy", TOY),
                            ("workloads", "toy-closed",
                             MIXES["tiny-closed"])):
        os.makedirs(os.path.join(root, "toy", sub))
        with open(os.path.join(root, "toy", sub, name + ".json"), "w") as f:
            json.dump(data, f)
    bench = dict(real, root=root, paths=real["paths"] + ["toy"])
    bench["configs"] = real["configs"] + [
        {"name": "toy", "file": "toy/configs/toy.json"}]
    bench["workloads"] = real["workloads"] + [
        {"name": "toy.closed", "config": "toy", "traffic": "toy-closed",
         "chips": 1}]
    for kind in ("end_to_end", "per_layer"):
        bench[kind] = [
            dict(m, workloads=m["workloads"] + ["toy.closed"])
            if "ouro-2.6b.decode-closed" in m.get("workloads", ()) else m
            for m in real[kind]]
    return bench


@pytest.fixture(scope="module")
def toy_bench(tmp_path_factory):
    return build_toy_bench(str(tmp_path_factory.mktemp("toy_bench")),
                           loader.load_benchmark())


def test_a_second_family_is_served_as_files_and_entries_alone(
        toy_bench, cpu_tpu_workers):
    family = loader.find_family(toy_bench, TOY)
    assert family["dir"] == os.path.join(toy_bench["root"], "toy",
                                         "families", "toy")
    line = run.run_cell(toy_bench, "toy.closed", seed=2**31 + 21,
                        seconds=3.0, trace=True, platform="cpu",
                        lm_class=TracedOnCpuLM)
    assert line["correct"], line["faults"]
    assert line["attempted"] > 0 and line["failed"] == 0
    gap = line["compared"]["served_logit_gap"]
    assert 0.0 <= gap["value"] <= gap["limit"]
    # the shared readers price the toy's steps by the toy's own costs
    # and read the phases its engine times under the program's names
    got = line["metrics"]
    assert got["serve_mfu_pct.closed"]["value"] > 0.0
    assert got["decode_occupancy_pct.closed"]["value"] > 0.0
    assert got["decode_device_wait_ms.closed"]["value"] > 0.0


def test_the_second_family_with_a_token_altered_is_not_correct(
        toy_bench, cpu_tpu_workers):
    line = run.run_cell(toy_bench, "toy.closed", seed=6, seconds=3.0,
                        trace=False, platform="cpu", lm_class=AlteredTokenLM)
    assert not line["correct"]
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


# ------------------------- the real file, grown by such a cell as data

GROWN_CELL = "toy-d1.decode-closed32"
GROWN_READERS = {       # per-layer entries that list the new cell alone
    "decode_occupancy_pct.closed32": (
        "readers", "decode_occupancy_pct", "program_counter",
        "replica and serve/decode_scheduler.py"),
    "decode_device_wait_ms.closed32": (
        "inside", "decode_device_wait_ms", "program_span",
        "engine: JaxSlotEngine, models/decode.py"),
    "decode_host_ms.closed32": (
        "inside", "decode_host_ms", "program_span",
        "engine: JaxSlotEngine, models/decode.py"),
    "prefill_stall_pct.closed32": (
        "inside", "prefill_stall_pct", "program_span",
        "replica and serve/decode_scheduler.py"),
    "device_idle_pct.closed32": (
        "readers", "device_idle_pct", "device_trace", "device"),
    "serve_mfu_pct.closed32": (
        "readers", "serve_mfu_pct", "program_span",
        "model step: models/transformer.py, models/decode.py"),
    "decode_roofline_pct.closed32": (
        "inside", "decode_roofline_pct", "device_trace",
        "model step: models/transformer.py, models/decode.py")}


def grow_by_a_cell(root: str, real: dict) -> dict:
    """What the next ``model_config`` PR does to the real file: a family
    (the toy's files), a configuration, a mix and one-line readers under
    a path of its own, one cell, its name appended to
    ``serve_tokens_per_s``'s ``workloads`` (the one change to an entry
    that is there), and per-layer entries that list it alone. The
    benchmark's own files stand where they stand in the repo."""
    shutil.copytree(BENCHMARKS, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(HERE, "toy"), os.path.join(root, "grown"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub, name, data in (("configs", "toy-d1", TOY),
                            ("workloads", "decode-closed32",
                             MIXES["tiny-closed"])):
        os.makedirs(os.path.join(root, "grown", sub))
        with open(os.path.join(root, "grown", sub, name + ".json"),
                  "w") as f:
            json.dump(data, f)
    os.makedirs(os.path.join(root, "grown", "metrics"))
    for name, (module, read, _, _) in GROWN_READERS.items():
        with open(os.path.join(root, "grown", "metrics", name + ".py"),
                  "w") as f:
            f.write(f"from benchmarks.{module} import {read} as read"
                    "  # noqa: F401\n")
    grown = copy.deepcopy(dict(real, root=root))
    grown["paths"].append("grown")
    grown["configs"].append({
        "name": "toy-d1", "source": "tests/bench/toy (no public model)",
        "file": "grown/configs/toy-d1.json", "reduced": [],
        "why": "a second family: an embedding, a norm, a tied head"})
    grown["workloads"].append({
        "name": GROWN_CELL, "config": "toy-d1",
        "traffic": "decode-closed32", "chips": 1,
        "why": "closed loop on the second family's own engine"})
    for m in grown["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(GROWN_CELL)
    grown["per_layer"] += [
        {"name": name, "unit": "ms" if name.split(".")[0].endswith("_ms")
         else "%", "better": "lower", "source": source, "layer": layer,
         "moves": "serve_tokens_per_s", "workloads": [GROWN_CELL]}
        for name, (_, _, source, layer) in GROWN_READERS.items()]
    return grown


def reported(bench, cells):
    """{(cell, traced): the names of the metrics it reports}."""
    return {(cell, traced): [m["name"] for m in loader.cell_metrics(
        bench, cell, traced)] for cell in cells for traced in (False, True)}


def test_the_real_benchmark_grows_by_a_cell_of_a_second_family(
        tmp_path, cpu_tpu_workers):
    real = loader.load_benchmark()
    grown = grow_by_a_cell(str(tmp_path / "grown"), real)
    keeps_the_contract(grown)
    every_cell_reports_what_the_contract_asks(grown)
    # the cells that were there report exactly what they report today
    cells = [w["name"] for w in real["workloads"]]
    assert reported(grown, cells) == reported(real, cells)
    assert reported(grown, [GROWN_CELL]) == {
        (GROWN_CELL, False): ["serve_tokens_per_s", "setup_s"],
        (GROWN_CELL, True): list(GROWN_READERS)}
    # the other tests' benchmarks are built from the grown file as from
    # the real one
    for build, made in ((build_tiny_bench, ["tiny.closed", "tiny.open",
                                            "tiny.train"]),
                        (build_toy_bench, cells + ["toy.closed"])):
        small = build(str(tmp_path / (build.__name__ + "_grown")), grown)
        same = build(str(tmp_path / (build.__name__ + "_real")), real)
        assert reported(small, made) == reported(same, made)
    # the new cell, served through the front door on the CPU
    line = run.run_cell(grown, GROWN_CELL, seed=2**31 + 23, seconds=3.0,
                        trace=True, platform="cpu", lm_class=TracedOnCpuLM)
    assert line["correct"], line["faults"]
    check_line(grown, GROWN_CELL, line, True)
    # every metric but the roofline, whose time is the device's
    # operations by name: the CPU's stand-in plane names none
    assert set(line["metrics"]) == set(GROWN_READERS) - {
        "decode_roofline_pct.closed32"}
    assert line["slice"]["steps"] > 0
    # ... which reads, through the same entry and file, where a trace
    # names the family's decode program (a TPU's does): two steps of 2
    # and 1 rows begun in the slice, 1.5 rows looked up a step
    vocab, hidden = TOY["vocab_size"], TOY["hidden_size"]
    step_bytes = 4.0 * ((vocab + 1) * hidden + 1.5 * hidden)
    obs = {"run": {"config": TOY, "family": loader.find_family(grown, TOY)},
           "device": {"kind": "TPU v5 lite"},
           "steps": [[10.1, 10.2, 2, 0], [10.3, 10.4, 1, 0],
                     [11.2, 11.3, 2, 0]],
           "decode_before": {"steps": 4, "phases": {}},
           "decode_after": {"steps": 8, "phases": {
               "serve.engine.wait": [4, 0.1],
               "serve.engine.rows_looked_up": [4, 6.0]}},
           "trace": {"window_s": 1.0, "slice": [10.0, 11.0],
                     "program_seconds": {"_greedy": 1e-6, "other": 9.0}}}
    read = loader.load_reader(grown, "decode_roofline_pct.closed32")
    assert read(obs) == pytest.approx(
        100.0 * 2 * step_bytes / peaks.PEAKS["TPU v5 lite"][
            "hbm_bytes_per_s"] / 1e-6)


# -------------------------------------- the weights are the parent's

def digest(tree) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode() + a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,want", [
    # computed on the parent commit 220f6f0 (benchmarks/reference.py's
    # seeded_params, before the family moved) for test_bench_run's TINY
    (7, "72973a5dcec3b815d1260e7b5f711112"
        "a0a4229b8aeb631879ee6918fe1a7ef5"),
    (2**31 + 3, "f1859bf7a6de3fb4217f988edda9cb19"
                "343da264e8459c84c3b9050cdd5ddaeb")])
def test_the_moved_family_makes_the_parents_weights(seed, want):
    reference = loader.family_module(
        loader.find_family(loader.load_benchmark(), TINY), "reference")
    params = reference.seeded_params(seed, reference.sizes_of(TINY))
    assert digest(params) == want


# ------------------------------------------------ no files, no result

def test_a_model_type_with_no_files_gives_no_result(
        toy_bench, monkeypatch, capsys):
    with open(os.path.join(toy_bench["root"], "toy", "configs",
                           "orphan.json"), "w") as f:
        json.dump(dict(TOY, model_type="orphan"), f)
    grown = dict(toy_bench)
    grown["configs"] = toy_bench["configs"] + [
        {"name": "orphan", "file": "toy/configs/orphan.json"}]
    grown["workloads"] = toy_bench["workloads"] + [
        {"name": "orphan.closed", "config": "orphan",
         "traffic": "toy-closed", "chips": 1}]
    monkeypatch.setattr(loader, "load_benchmark", lambda: grown)
    monkeypatch.setattr(run, "session", lambda chips: pytest.fail(
        "a session was started"))
    code = run.main(["--workload", "orphan.closed", "--seed", "3",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code == 1 and out.out == "" and "NO RESULT" in out.err
    for path in grown["paths"]:
        assert os.path.join(grown["root"], path, "families", "orphan",
                            "reference.py") in out.err
    with pytest.raises(loader.BenchmarkError, match="no model_type"):
        loader.find_family(toy_bench, {"hidden_size": 8})
    # a family that lacks one of its three files is no family
    half = os.path.join(toy_bench["root"], "toy", "families", "half")
    os.makedirs(half)
    open(os.path.join(half, "reference.py"), "w").close()
    with pytest.raises(loader.BenchmarkError, match="lacks"):
        loader.find_family(toy_bench, {"model_type": "half"})


# ------------------------------------- no shared module knows a block

SHARED = sorted(glob.glob(os.path.join(BENCHMARKS, "*.py"))
                + glob.glob(os.path.join(BENCHMARKS, "metrics", "*.py")))
FAMILIES = sorted(glob.glob(os.path.join(BENCHMARKS, "families", "*"))
                  + glob.glob(os.path.join(HERE, "toy", "families", "*")))
PROGRAM_NAMES = {"TransformerConfig", "ParallelConfig", "JaxSlotEngine",
                 "slot_prefill", "init_slot_cache"}
BLOCK_KEYS = {"num_attention_heads", "num_key_value_heads", "head_dim",
              "intermediate_size", "rms_norm_eps", "attn_norm", "wq", "wk",
              "wv", "wo", "mlp_norm", "w_gate", "w_up", "w_down"}


def imports_of(tree):
    """Every module or name an ``import`` statement of the file names:
    ``from a.b import c`` gives ``a.b`` and ``a.b.c``; a relative import
    keeps its dots."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            module = "." * node.level + (node.module or "")
            found.add(module)
            joint = "" if module.endswith(".") else "."
            found.update(module + joint + alias.name for alias in node.names)
    return found


def test_the_import_scan_sees_every_form_of_import():
    tree = ast.parse("import a.b\nfrom c.d import e\nfrom . import f\n"
                     "from .g import h\ndef k():\n    from i import j\n")
    assert imports_of(tree) == {"a.b", "c.d", "c.d.e", ".", ".f", ".g",
                                ".g.h", "i", "i.j"}


def test_no_shared_module_knows_a_block():
    assert len(SHARED) > 30
    for path in SHARED:
        with open(path) as f:
            tree = ast.parse(f.read())
        for name in imports_of(tree):
            assert not name.startswith("ray_tpu.models"), (path, name)
            assert name.rsplit(".", 1)[-1] not in PROGRAM_NAMES | {
                "make_train_step"}, (path, name)
        names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | {
            n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
        assert not names & PROGRAM_NAMES, path
        # a configuration key or a leaf is named by a string where it is
        # read
        strings = {n.value for n in ast.walk(tree)
                   if isinstance(n, ast.Constant)
                   and isinstance(n.value, str) and "\n" not in n.value}
        assert not strings & BLOCK_KEYS, path


@pytest.mark.parametrize("where", FAMILIES, ids=[
    os.path.basename(p) for p in FAMILIES])
def test_a_familys_reference_stands_apart_from_the_program(where):
    assert len(FAMILIES) >= 2
    for kind in loader.FAMILY_KINDS:
        assert os.path.isfile(os.path.join(where, kind + ".py")), kind
    found = {}
    for kind in ("reference", "costs"):
        with open(os.path.join(where, kind + ".py")) as f:
            found[kind] = imports_of(ast.parse(f.read()))
        for name in found[kind]:
            assert not name.startswith("ray_tpu"), (kind, name)
            assert "program" not in name.split("."), (kind, name)
    # the driver's process reads costs.py and stays off jax
    assert not any(n.split(".")[0] == "jax" for n in found["costs"])
