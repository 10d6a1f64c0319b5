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
import glob
import hashlib
import json
import os
import shutil
import sys

import cloudpickle
import pytest
from test_bench_run import (MIXES, TINY, AlteredTokenLM,  # noqa: F401
                            TracedOnCpuLM, compile_cache, cpu_tpu_workers)

from benchmarks import loader, run

cloudpickle.register_pickle_by_value(sys.modules[__name__])

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.join(loader.ROOT, "benchmarks")
TOY = {"model_type": "toy", "hidden_size": 32, "norm_eps": 1e-6,
       "tie_word_embeddings": True, "vocab_size": 128}


# --------------------------------------------- a second family, as files

@pytest.fixture(scope="module")
def toy_bench(tmp_path_factory):
    """A benchmark root of its own. The shared harness is the repo's
    (``benchmarks`` on the path, its metric readers copied); the toy
    family, its configuration, its mix and its cell are new files under
    a new path and new entries."""
    root = str(tmp_path_factory.mktemp("toy_bench"))
    real = loader.load_benchmark()
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
    # the shared readers price the toy's steps by the toy's own costs;
    # its engine records no phases of its own, so those readers find
    # nothing and their metrics are left out
    got = line["metrics"]
    assert got["serve_mfu_pct.closed"]["value"] > 0.0
    assert got["decode_occupancy_pct.closed"]["value"] > 0.0
    assert "decode_device_wait_ms.closed" not in got


def test_the_second_family_with_a_token_altered_is_not_correct(
        toy_bench, cpu_tpu_workers):
    line = run.run_cell(toy_bench, "toy.closed", seed=6, seconds=3.0,
                        trace=False, platform="cpu", lm_class=AlteredTokenLM)
    assert not line["correct"]
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


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
