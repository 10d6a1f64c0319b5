"""The decode step's device time by named part, as the benchmark reads
it (``benchmarks/inside_parts.py``): the join of the compiled step's own
table (``obs["decode_after"]["parts"]``, the program's) with the device
trace's operations, on hand-built observations against hand-worked
numbers; nothing to read where the table, the trace or the steps are
missing; the fourteen entries that wait in ``put_off/decode-parts.json``
with their readers; and the tiny closed-loop cell traced on the CPU,
whose observations carry the table to the readers.
"""

import copy
import json
import os

import pytest
from test_bench_run import (TracedOnCpuLM, compile_cache,  # noqa: F401
                            cpu_tpu_workers, tiny_bench)
from test_bench_units import (every_cell_reports_what_the_contract_asks,
                              keeps_the_contract)

from benchmarks import inside_parts, loader, run

PROGRAM = "slot_decode_step"
CELLS = {"closed": "ouro-2.6b.decode-closed",
         "reason": "mimo-v2-flash-ep16-d7.reason-closed",
         "rollout": "jamba2-3b.rollout-closed"}
LAYER = "model step: models/transformer.py, models/decode.py"


@pytest.fixture(scope="module")
def waiting():
    with open(os.path.join(loader.ROOT, "benchmarks", "put_off",
                           "decode-parts.json")) as f:
        return json.load(f)["per_layer"]


@pytest.fixture(scope="module")
def grown(waiting):
    """The repo's ``BENCHMARK.json`` with the entries that wait."""
    bench = loader.load_benchmark()
    bench["per_layer"] = bench["per_layer"] + copy.deepcopy(waiting)
    return bench


# ------------------------------------------------ hand-built observations

# two runs; a fusion in each part; a while, which holds its body's
# operations; an operation the table lacks; one outside every run and
# part; one of another program under an instruction's name
TABLE = {
    "fusion.1": [None, "embed"],
    "slice.1": ["run0", "layer_weights"], "copy.1": ["run0", "layer_weights"],
    "slice.2": ["run1", "layer_weights"],
    "fusion.2": ["run0", "qkv"], "fusion.3": ["run1", "qkv"],
    "decode_attend.5": ["run0", "full_attention"],
    "fusion.4": ["run1", "window_attention"],
    "fusion.5": ["run0", "attn_out"],
    "fusion.6": ["run0", "mlp"], "fusion.7": ["run1", "mlp"],
    "fusion.8": ["run1", "router"], "fusion.9": ["run1", "experts"],
    "fusion.10": ["run1", "mamba_mixer"], "fusion.11": ["run1", "ssm_step"],
    "fusion.12": [None, "head"],
    "copy-start.1": [None, None],
    "never_ran.1": ["run0", "mlp"],
}
OPS = {      # seconds of the slice, calls
    "fusion.1": [0.010, 4],
    "slice.1": [0.030, 8], "copy.1": [0.020, 8], "slice.2": [0.050, 8],
    "fusion.2": [0.040, 8], "fusion.3": [0.020, 8],
    "decode_attend.5": [0.100, 8], "fusion.4": [0.060, 8],
    "fusion.5": [0.015, 8],
    "fusion.6": [0.200, 8], "fusion.7": [0.100, 8],
    "fusion.8": [0.025, 8], "fusion.9": [0.175, 8],
    "fusion.10": [0.070, 8], "fusion.11": [0.130, 8],
    "fusion.12": [0.045, 4],
    "copy-start.1": [0.005, 4],     # listed, in no part: unscoped
    "while.3": [0.700, 4],          # not listed: its body is above
    "made_later.2": [0.004, 4],     # not listed
}
NAMED = 1.090       # the sum of the operations above that lie in a part
WHOLE = 1.250       # the union: the loops' gaps and the unlisted beside


def hand_obs(bench, table=TABLE):
    totals = {f"{PROGRAM}/{name}": list(v) for name, v in OPS.items()}
    totals["slot_prefill/fusion.6"] = [9.0, 9]      # another program's
    return {
        "run": {"config": {"model_type": "ouro"},
                "family": loader.find_family(bench, {"model_type": "ouro"})},
        "steps": [[10.0, 10.1, 2, 7], [10.4, 10.5, 2, 9],
                  [10.6, 10.7, 1, 5], [10.9, 11.2, 2, 11],
                  [11.0, 11.1, 2, 13]],     # four begin in the slice
        "decode_before": {"steps": 3},
        "decode_after": {"steps": 9, "parts": {PROGRAM: table}},
        "trace": {"window_s": 1.0, "slice": [10.0, 11.0],
                  "op_totals": totals,
                  "program_seconds": {PROGRAM: WHOLE, "slot_prefill": 9.0}}}


def test_the_join_gives_seconds_by_run_and_part(grown):
    got = inside_parts.part_seconds(hand_obs(grown))
    assert got == {
        (None, "embed"): pytest.approx(0.010),
        ("run0", "layer_weights"): pytest.approx(0.050),
        ("run1", "layer_weights"): pytest.approx(0.050),
        ("run0", "qkv"): pytest.approx(0.040),
        ("run1", "qkv"): pytest.approx(0.020),
        ("run0", "full_attention"): pytest.approx(0.100),
        ("run1", "window_attention"): pytest.approx(0.060),
        ("run0", "attn_out"): pytest.approx(0.015),
        ("run0", "mlp"): pytest.approx(0.200),
        ("run1", "mlp"): pytest.approx(0.100),
        ("run1", "router"): pytest.approx(0.025),
        ("run1", "experts"): pytest.approx(0.175),
        ("run1", "mamba_mixer"): pytest.approx(0.070),
        ("run1", "ssm_step"): pytest.approx(0.130),
        (None, "head"): pytest.approx(0.045)}
    # the while, the unlisted and the one in no part are in none of them
    assert sum(got.values()) == pytest.approx(NAMED)


WORKED = {      # ms a step over the four steps begun in the slice, or %
    "decode_layer_weights_ms": 1e3 * (0.030 + 0.020 + 0.050) / 4,
    "decode_qkv_ms": 1e3 * (0.040 + 0.020) / 4,
    "decode_attention_ms": 1e3 * (0.100 + 0.060) / 4,
    "decode_mlp_ms": 1e3 * (0.200 + 0.100) / 4,
    "decode_experts_ms": 1e3 * (0.025 + 0.175) / 4,
    "decode_head_ms": 1e3 * 0.045 / 4,
    "decode_mixer_ms": 1e3 * (0.070 + 0.130) / 4,
    "decode_ssm_step_ms": 1e3 * 0.130 / 4,
    "decode_unscoped_pct": 100.0 * (WHOLE - NAMED) / WHOLE,
}
ENTRIES = [
    "decode_layer_weights_ms.closed", "decode_qkv_ms.closed",
    "decode_attention_ms.closed", "decode_mlp_ms.closed",
    "decode_unscoped_pct.closed", "decode_layer_weights_ms.reason",
    "decode_attention_ms.reason", "decode_experts_ms.reason",
    "decode_head_ms.reason", "decode_unscoped_pct.reason",
    "decode_mixer_ms.rollout", "decode_ssm_step_ms.rollout",
    "decode_mlp_ms.rollout", "decode_unscoped_pct.rollout"]


@pytest.mark.parametrize("name", ENTRIES)
def test_an_entrys_reader_against_the_hand_worked_number(grown, name):
    read = loader.load_reader(grown, name)
    assert read(hand_obs(grown)) == pytest.approx(
        WORKED[name.rsplit(".", 1)[0]])


def test_the_parts_and_the_unscoped_share_add_up_to_the_program(grown):
    obs = hand_obs(grown)
    every = sorted({part for _, part in TABLE.values() if part})
    ms = inside_parts.part_ms(obs, every)
    unscoped = inside_parts.unscoped_pct(obs)
    assert ms / (1.0 - unscoped / 100.0) == pytest.approx(1e3 * WHOLE / 4)
    # a container counted beside its body drives the share below zero
    twice = hand_obs(grown, dict(TABLE, **{"while.3": ["run0", "qkv"]}))
    assert inside_parts.unscoped_pct(twice) < 0.0


def gone(obs, *path):
    node = obs
    for key in path[:-1]:
        node = node[key]
    del node[path[-1]]


@pytest.mark.parametrize("why,change", [
    ("the program before PR 38 gives no table",
     lambda o: gone(o, "decode_after", "parts")),
    ("an engine that has not stepped yet",
     lambda o: o["decode_after"].update(parts={PROGRAM: None})),
    ("a table of another program alone",
     lambda o: o["decode_after"].update(parts={"_greedy": TABLE})),
    ("an untraced run", lambda o: o.update(trace=None)),
    ("a trace that names no operation of the program",
     lambda o: o["trace"].update(op_totals={
         "?/fusion.6": [1.0, 4], "slot_prefill/fusion.6": [9.0, 9]})),
    ("no step begun in the slice",
     lambda o: o.update(steps=[[9.0, 9.5, 2, 7], [11.5, 11.6, 2, 9]])),
])
def test_nothing_to_read_reads_none(grown, why, change):
    obs = hand_obs(grown)
    change(obs)
    for name in ENTRIES:
        assert loader.load_reader(grown, name)(obs) is None, (why, name)
    assert inside_parts.part_ms(obs, ("mlp",)) is None


def test_a_family_with_another_decode_program_reads_none(grown):
    """The toy family's engine compiles nothing and offers no table;
    its ``costs.DECODE_PROGRAM`` names another program."""
    obs = hand_obs(grown)
    obs["run"]["family"] = {
        "model_type": "toy", "dir": os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "toy", "families",
            "toy")}
    assert inside_parts.part_seconds(obs) is None
    assert inside_parts.unscoped_pct(obs) is None


# ------------------------------------------------- the entries that wait

def test_every_waiting_entry_has_its_reader_and_one_accepted_cell(
        waiting, grown):
    assert [m["name"] for m in waiting] == ENTRIES
    accepted = {w["name"] for w in loader.load_benchmark()["workloads"]}
    for m in waiting:
        stem, mix = m["name"].rsplit(".", 1)
        assert m["workloads"] == [CELLS[mix]] and CELLS[mix] in accepted
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "device_trace", LAYER, "serve_tokens_per_s", "lower")
        assert m["unit"] == ("%" if stem.endswith("_pct") else "ms")
        assert os.path.isfile(os.path.join(
            loader.ROOT, "benchmarks", "metrics", m["name"] + ".py"))
        assert callable(loader.load_reader(grown, m["name"]))
    # none is entered yet, and entered they keep the contract
    entered = {m["name"] for m in loader.load_benchmark()["per_layer"]}
    assert not entered & set(ENTRIES)
    keeps_the_contract(grown)
    every_cell_reports_what_the_contract_asks(grown)
    for mix, cell in CELLS.items():
        mine = [m["name"] for m in loader.cell_metrics(grown, cell, True)
                if m["name"] in ENTRIES]
        assert mine == [n for n in ENTRIES if n.endswith("." + mix)]


def test_the_layer_is_one_the_benchmark_already_names(waiting):
    named = {m["layer"] for m in loader.load_benchmark()["per_layer"]}
    assert {m["layer"] for m in waiting} <= named


# ----------------------------------- the tiny closed-loop cell, traced

def test_the_traced_tiny_cell_hands_the_table_to_the_readers(
        tiny_bench, grown, cpu_tpu_workers, monkeypatch):
    seen = {}
    real = loader.read_metrics

    def keep(bench, cell, trace, obs):
        seen["obs"] = obs
        return real(bench, cell, trace, obs)

    monkeypatch.setattr(loader, "read_metrics", keep)
    line = run.run_cell(tiny_bench, "tiny.closed", seed=2**31 + 37,
                        seconds=3.0, trace=True, platform="cpu",
                        lm_class=TracedOnCpuLM)
    assert line["correct"], line["faults"]
    obs = seen["obs"]
    # the program's table came through the benchmark's timing wrapper
    # and the front door, whole
    table = obs["decode_after"]["parts"][PROGRAM]
    assert len(table) > 20
    assert {"qkv", "full_attention", "attn_out", "mlp", "head",
            "layer_weights"} <= {part for _, part in table.values()}
    assert {run_ for run_, _ in table.values()} == {None, "run0"}
    # on the CPU the host's plane stands in for the device's and names
    # no operation of a program: the readers raise nothing, and read a
    # number only where the trace does name them
    names = [name for name in obs["trace"]["op_totals"]
             if name.startswith(PROGRAM + "/")]
    for name in ENTRIES:
        value = loader.load_reader(grown, name)(obs)
        if not names:
            assert value is None, name
        else:
            assert value is None or isinstance(value, float), name
