"""Fast checks of the benchmark's own yardstick: the names and units in
BENCHMARK.json, the loader, the traffic generator, the trace reduction
on a hand-built trace, the FLOP and byte counts against hand-worked
shapes, and the reference with its lower-precision control at a size a
test run can hold. Nothing here describes a TPU topology or starts a
session.
"""

import json
import os
import re
import shutil

import pytest

from benchmarks import loader, peaks, readers, trace, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return loader.load_benchmark()


@pytest.fixture(scope="module")
def ouro(bench):
    """Where the ``ouro`` family's files are, as ``run["family"]``."""
    return loader.find_family(bench, {"model_type": "ouro"})


# ------------------------------------------------------- BENCHMARK.json

def keeps_the_contract(bench):
    """The names, units, sources and counts the contract fixes, on any
    ``BENCHMARK.json`` (the repo's, or one grown by a later PR's cell)."""
    keys = set(bench) - {"root"}
    assert keys == {"command", "paths", "run_seconds", "configs",
                    "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for metric in bench["end_to_end"] + bench["per_layer"]:
        names.append(metric["name"])
        assert NAME.match(metric["name"]), metric
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    assert len(names) == len(set(names))
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in end_to_end
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in end_to_end
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == len(set(cells))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)


def test_benchmark_json_keeps_the_contract(bench):
    keeps_the_contract(bench)


def every_cell_reports_what_the_contract_asks(bench):
    for w in bench["workloads"]:
        flat = {m["name"] for m in loader.cell_metrics(bench, w["name"],
                                                       trace=False)}
        traced = loader.cell_metrics(bench, w["name"], trace=True)
        assert "setup_s" in flat and len(flat) >= 2
        assert any("mfu" in re.split(r"[_.]", m["name"]) for m in traced)
        assert any(m["name"].startswith("device_idle_pct") for m in traced)
        for m in traced:
            assert m["moves"] in flat       # it moves a metric reported here
            loader.load_reader(bench, m["name"])
        loader.load_traffic(bench, w["traffic"])


def test_every_cell_reports_what_the_contract_asks(bench):
    every_cell_reports_what_the_contract_asks(bench)


def test_configuration_files_keep_the_published_sizes(bench):
    rows = {}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        for text in f:
            row = json.loads(text)
            rows[row["source_url"]] = row["config"]
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads", "vocab_size")
    for entry in bench["configs"]:
        config = loader.load_config(bench, entry["name"])
        published = rows[entry["source"]]
        changed = {k for k, v in published.items() if config.get(k) != v}
        assert changed <= set(entry["reduced"]), changed
        assert not set(entry["reduced"]) & set(widths)
        costs = loader.family_module(loader.find_family(bench, config),
                                     "costs")
        assert costs.n_params(config) > 0


@pytest.mark.parametrize("config,millions", [
    ("ouro-2.6b", 2567), ("ouro-2.6b-d12", 717)])
def test_parameter_counts(bench, ouro, config, millions):
    costs = loader.family_module(ouro, "costs")
    assert round(costs.n_params(loader.load_config(bench, config)) / 1e6) \
        == millions


# ------------------------------------------------------------ the loader

def test_a_cell_is_added_as_files_and_entries_alone(bench, tmp_path):
    """A new configuration, traffic mix and per-layer metric: new files
    and new entries, no edit to a file that is there."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(loader.ROOT, "benchmarks", "metrics"),
                    os.path.join(root, "benchmarks", "metrics"))
    extra = os.path.join(root, "extra")
    for sub in ("configs", "workloads", "metrics"):
        os.makedirs(os.path.join(extra, sub))
    with open(os.path.join(extra, "configs", "new.json"), "w") as f:
        json.dump({"hidden_size": 8}, f)
    with open(os.path.join(extra, "workloads", "new-mix.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed"}, f)
    with open(os.path.join(extra, "metrics", "steps_seen.new.py"), "w") as f:
        f.write("def read(obs):\n    return len(obs['steps']) or None\n")
    grown = dict(bench, root=root, paths=bench["paths"] + ["extra"])
    grown["configs"] = bench["configs"] + [
        {"name": "new", "file": "extra/configs/new.json"}]
    grown["workloads"] = bench["workloads"] + [
        {"name": "new.new-mix", "config": "new", "traffic": "new-mix",
         "chips": 1}]
    grown["per_layer"] = bench["per_layer"] + [
        {"name": "steps_seen.new", "unit": "steps", "moves": "setup_s",
         "workloads": ["new.new-mix"]}]
    cell = loader.find_cell(grown, "new.new-mix")
    assert loader.load_config(grown, cell["config"]) == {"hidden_size": 8}
    assert loader.load_traffic(grown, cell["traffic"])["loop"] == "closed"
    assert [m["name"] for m in loader.cell_metrics(
        grown, "new.new-mix", trace=True)] == ["steps_seen.new"]
    assert loader.read_metrics(grown, "new.new-mix", True,
                               {"steps": [1, 2]}) == {
        "steps_seen.new": {"value": 2.0, "unit": "steps"}}
    # a reader that finds nothing is left out of the line, never 0
    assert loader.read_metrics(grown, "new.new-mix", True,
                               {"steps": []}) == {}
    with pytest.raises(loader.BenchmarkError):
        loader.find_cell(grown, "no.such-cell")


# ------------------------------------------------------------- traffic

OPEN = {"kind": "serve", "loop": "open", "rate_per_s": 6.0,
        "prompt_lengths": [512, 640, 768, 896],
        "prompt_weights": [0.4, 0.3, 0.2, 0.1],
        "output_tokens": {"min": 8, "max": 24}, "cycle": 120}


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.serve_plan(OPEN, seed=3, seconds=40.0)
    b = traffic.serve_plan(OPEN, seed=2**31 + 77, seconds=40.0)
    assert a == traffic.serve_plan(OPEN, seed=3, seconds=40.0)
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    cycle = traffic.cycle_of(OPEN)
    assert sorted(r["prompt_len"] for r in cycle) == \
        [512] * 48 + [640] * 36 + [768] * 24 + [896] * 12
    assert {r["max_tokens"] for r in cycle} == set(range(8, 25))
    # arrivals at the file's rate, every one inside the window, in order
    for plan in (a, b):
        assert abs(len(plan) / 40.0 - 6.0) < 0.5
        dues = [r["due_s"] for r in plan]
        assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 40.0
    assert traffic.prompt_tokens(2**31 + 5, 4, 16, 49152) == \
        traffic.prompt_tokens(2**31 + 5, 4, 16, 49152)
    assert traffic.prompt_tokens(1, 4, 16, 49152) != \
        traffic.prompt_tokens(2, 4, 16, 49152)


def test_the_check_rereads_the_longest_request():
    done = [{"index": i, "prompt_len": 128 + 128 * (i % 2),
             "tokens": [0] * (10 + i)} for i in range(20)]
    sample = traffic.check_sample(7, done, 4)
    assert len(sample) == 4 and sample[0]["index"] == 19
    assert sample == traffic.check_sample(7, done, 4)
    assert traffic.check_sample(7, [], 4) == []


# ------------------------------------------------- the trace reduction

def hand_built_trace():
    """A 20 us slice. One program runs over [1,8): a while over [1,7)
    that holds two fusions, [1,3) and [4,6), then a copy [7,8). A second
    program's fusion [12,13) and one that straddles the slice's end
    [19,22). Host: a step span over [8,15) with a transfer [9,12) inside
    it; another thread shares its name."""
    us = 1000.0
    hlo = "%fusion.1 = bf16[8,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8] %p)"
    return {
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(123)", 1 * us, 7 * us),
                            ("jit_other(77)", 12 * us, 10 * us)],
            "XLA Ops": [
                ("%while.4 = (s32[]) while(%tuple.1)", 1 * us, 6 * us),
                (hlo, 1 * us, 2 * us), (hlo, 4 * us, 2 * us),
                ("%copy.2 = bf16[8] copy(%x)", 7 * us, 1 * us),
                (hlo, 12 * us, 1 * us), ("%copy.9", 19 * us, 3 * us),
                ("%before", -5 * us, 2 * us)],
            "Steps": [("ignored", 0.0, 20 * us)]},
        "/host:CPU": {
            "python3": [(trace.WINDOW, 0.0, 20 * us)],
            "python3+": [("engine.step", 8 * us, 7 * us),
                         ("np.asarray(jax.Array)", 9 * us, 3 * us),
                         ("other", 0.0, 8 * us)]},
    }


def test_busy_union_clipping_and_idle_share():
    out = trace.reduce_trace(hand_built_trace())
    assert out["window_s"] == pytest.approx(20e-6)
    # [1,8) + [12,13) + [19,20) = 9 us busy of 20
    assert out["busy_s"] == pytest.approx(9e-6)
    assert readers.device_idle_pct({"trace": out}) == pytest.approx(55.0)
    assert readers.device_idle_pct({"trace": None}) is None
    # an operation's own time, under its program's name: the while keeps
    # the 2 us its body leaves, and the two programs' fusion.1 stay apart
    ops = dict(map(tuple, out["device_ops"]))
    assert ops == {"step/fusion.1": pytest.approx(4e-6),
                   "step/while.4": pytest.approx(2e-6),
                   "step/copy.2": pytest.approx(1e-6),
                   "other/fusion.1": pytest.approx(1e-6),
                   "other/copy.9": pytest.approx(1e-6)}
    assert out["device_ops"][0][0] == "step/fusion.1"
    assert out["op_totals"]["step/while.4"] == [pytest.approx(6e-6), 1]
    assert "?/before" not in out["op_totals"]
    # a program's device time is a union: the while's 6 us hold its
    # body's 4, and the copy adds 1; the other program's 1 + 1 clipped
    assert out["program_seconds"] == {"step": pytest.approx(7e-6),
                                      "other": pytest.approx(2e-6)}
    # idle: [0,1), [8,12), [13,19); the middle of [8,12) lies in the
    # step's transfer, that of [13,19) outside the step
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps == {
        "in engine.step: np.asarray": pytest.approx(4e-6),
        "between host spans": pytest.approx(6e-6),
        "between operations, under 2 us each": pytest.approx(1e-6)}


def test_idle_gaps_take_the_programs_own_span_names():
    """The program's ``serve.*`` spans lie inside the benchmark's and on
    other threads beside them: a gap is labelled by the innermost span
    over its middle, whichever thread it is on. Device busy [0,2),
    [6,7), [11,12), [16,20): gaps with middles 4, 9 and 14."""
    us = 1000.0
    op = "%fusion.1 = bf16[8] fusion(bf16[8] %p)"
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(1)", 0.0, 20 * us)],
            "XLA Ops": [(op, 0.0, 2 * us), (op, 6 * us, 1 * us),
                        (op, 11 * us, 1 * us), (op, 16 * us, 4 * us)]},
        "/host:CPU": {
            "tracer": [(trace.WINDOW, 0.0, 20 * us)],
            # the scheduler's loop: a step, then an admission's prefill
            "loop": [("serve.step", 1 * us, 6.5 * us),
                     ("serve.admit", 7.6 * us, 8 * us),
                     ("serve.prefill", 7.8 * us, 7 * us)],
            # the executor: the benchmark's wrapper around each call of
            # the engine, which times its own phases inside the step
            "executor": [("engine.step", 1.5 * us, 5.5 * us),
                         ("serve.engine.dispatch", 1.6 * us, 0.4 * us),
                         ("serve.engine.wait", 2 * us, 4.5 * us),
                         ("np.asarray(jax.Array)", 2.1 * us, 4 * us),
                         ("engine.prefill", 8 * us, 2.5 * us),
                         ("PjitFunction(slot_prefill)", 8.2 * us, 2 * us)]},
    }
    gaps = dict(map(tuple, trace.reduce_trace(planes)["idle_gaps"]))
    assert gaps == {
        "in serve.engine.wait": pytest.approx(4e-6),
        "in engine.prefill: PjitFunction": pytest.approx(4e-6),
        "in serve.prefill": pytest.approx(4e-6)}
    assert trace.labels_gaps("train.report") and trace.labels_gaps(
        "engine.step") and not trace.labels_gaps("observe.step")


def test_an_idle_gap_is_labelled_by_what_covers_most_of_it():
    """A traced train step's boundary gap: the device stops at 10 while
    the host still sits in the step's ``float(loss)`` until 12.6, the
    next step's span opens at 13 and its first operation runs at 15.
    The gap's middle, 12.5 or 12.7 from one run to the next, fell now
    inside the fetch, now between the spans; most of the gap is the
    fetch's either way. Then a gap that three spans share: the longest
    share names it, not the one over the middle."""
    us = 1000.0
    op = "%fusion.1 = bf16[8] fusion(bf16[8] %p)"

    def gaps_of(fetch_end, first_op):
        return dict(map(tuple, trace.reduce_trace({
            "/device:TPU:0": {
                "XLA Modules": [("jit_local_step(1)", 0.0, 40 * us)],
                "XLA Ops": [(op, 0.0, 10 * us),
                            (op, first_op * us, (40 - first_op) * us)]},
            "/host:CPU": {
                "worker": [(trace.WINDOW, 0.0, 40 * us),
                           ("train.step", 1 * us, (fetch_end - 1) * us),
                           ("np.asarray(jax.Array)", 9 * us,
                            (fetch_end - 9) * us),
                           ("train.step", 13 * us, 20 * us),
                           ("PjitFunction(local_step)", 14.5 * us, 1 * us)]},
        })["idle_gaps"]))

    for fetch_end, first_op in ((12.6, 15.0), (12.4, 15.4)):
        assert gaps_of(fetch_end, first_op) == {
            "in train.step: np.asarray": pytest.approx(
                (first_op - 10) * 1e-6)}
    # 10..11 the fetch, 11..13 nothing, 13..14.5 and 15.5..19 the next
    # step's span: 5 us of 9, though the middle (14.5+) is the dispatch
    assert gaps_of(11.0, 19.0) == {"in train.step": pytest.approx(9e-6)}
    # no span over most of it
    assert gaps_of(11.0, 14.0) == {
        "between host spans": pytest.approx(4e-6)}


def test_a_trace_without_its_marks_is_refused():
    planes = hand_built_trace()
    del planes["/device:TPU:0"]
    with pytest.raises(ValueError, match="no /device:TPU:"):
        trace.reduce_trace(planes)
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_trace({"/device:TPU:0": {"XLA Ops": []}})


def test_threads_of_one_name_stay_apart(tmp_path, monkeypatch):
    class Ev:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        name = "/host:CPU"
        lines = [Line("python3", [Ev("a", 0, 5)]),
                 Line("python3", [Ev("b", 1, 2)])]

    class Data:
        planes = [Plane]

    import jax.profiler

    run_dir = tmp_path / "plugins" / "profile" / "t0"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: Data))
    assert trace.read_xplane(str(tmp_path)) == {"/host:CPU": {
        "python3": [("a", 0.0, 5.0)], "python3+": [("b", 1.0, 2.0)]}}
    with pytest.raises(FileNotFoundError):
        trace.read_xplane(str(tmp_path / "plugins"))


# ---------------------------------------------------- FLOPs and bytes

SMALL = {"model_type": "ouro", "hidden_size": 8, "intermediate_size": 16, "head_dim": 4,
         "num_attention_heads": 2, "num_hidden_layers": 3, "vocab_size": 10}


def test_flop_counts_against_a_hand_worked_shape(ouro):
    costs = loader.family_module(ouro, "costs")
    # a layer: 4*8*8 + 3*8*16 + 2*8 = 656; embedding 80; final norm 8
    assert costs.n_params(SMALL) == 80 + 3 * 656 + 8 == 2056
    # attention: 4 * (2 heads * 4) * 3 layers = 96 FLOPs a (query, key) pair
    assert costs.attention_flops(SMALL, 10) == 960
    # a 4-token prefill: 2*(2056-80)*4 for the layers, 2*80 for the one
    # row of logits, 10 causal pairs
    assert costs.forward_flops(SMALL, 4, 10, logit_rows=1) == \
        2 * 1976 * 4 + 160 + 960
    # a train step of 2 x 4 tokens: 6*N*8 plus 3x the forward's attention
    assert costs.train_flops(SMALL, 2, 4) == 6 * 2056 * 8 + 3 * 96 * 20
    assert costs.flash_shape(dict(SMALL, torch_dtype="bfloat16"),
                             {"batch": 2, "seq": 4}) == (2, 4, 2, 4, 2)
    fwd = peaks.flash_fwd_cost(1, 4, 2, 4)
    assert fwd["flops"] == 4 * 4 * (2 * 10)
    assert fwd["bytes"] == 4 * (4 * 2 * 4) * 2 + 4 * 2 * 4
    assert peaks.flash_bwd_cost(1, 4, 2, 4)["flops"] == 2.5 * fwd["flops"]
    # K/V heads of their own: q and o (and do, dq) at the query's 2
    # heads, k and v (and dk, dv) at 1; the FLOPs are the query's. None
    # is the query's heads, to the digit
    grouped = peaks.flash_fwd_cost(1, 4, 2, 4, 2, 1)
    assert grouped["flops"] == fwd["flops"]
    assert grouped["bytes"] == (2 * (4 * 2 * 4) + 2 * (4 * 1 * 4)) * 2 \
        + 4 * 2 * 4
    assert peaks.flash_bwd_cost(1, 4, 2, 4, 2, 1)["bytes"] == \
        (4 * (4 * 2 * 4) + 4 * (4 * 1 * 4)) * 2 + 8 * 2 * 4
    for cost in (peaks.flash_fwd_cost, peaks.flash_bwd_cost):
        assert cost(4, 2048, 16, 128, 2) == cost(4, 2048, 16, 128, 2, 16) \
            == cost(4, 2048, 16, 128, 2, None)
    assert peaks.flash_fwd_cost(4, 2048, 16, 128, 2)["bytes"] == \
        4.0 * 4 * 2048 * 16 * 128 * 2 + 4.0 * 4 * 16 * 2048
    assert peaks.flash_bwd_cost(4, 2048, 16, 128, 2)["bytes"] == \
        8.0 * 4 * 2048 * 16 * 128 * 2 + 8.0 * 4 * 16 * 2048
    # a decode step of 2 rows that attend 7 positions between them, in
    # bfloat16: every parameter once, and K and V of 7 + 2 positions at
    # 3 layers x 2 heads x 4 x 2 (K, V) x 2 bytes = 96 bytes each
    assert costs.DECODE_PROGRAM == "slot_decode_step"
    assert costs.decode_step_bytes(dict(SMALL, torch_dtype="bfloat16"),
                                   2, 7, {}) == 2056 * 2 + 9 * 96
    v5e = peaks.peaks_of("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"],
            v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert peaks.roofline_seconds({"flops": 197e12, "bytes": 1.0}, v5e) == \
        {"seconds": 1.0, "bound": "compute"}
    with pytest.raises(ValueError, match="no peaks on record"):
        peaks.peaks_of("TPU v9")


def test_mfu_reads_the_traced_slice_alone(ouro):
    costs = loader.family_module(ouro, "costs")
    obs = {"trace": {"window_s": 1.0, "busy_s": 0.5, "slice": [10.0, 11.0]},
           "device": {"kind": "TPU v5 lite"},
           "run": {"config": SMALL, "family": ouro,
                   "traffic": {"batch": 2, "seq": 4}},
           "steps": [[9.0, 9.5, 2, 8], [10.2, 10.4, 2, 8]],
           "prefills": [[10.5, 10.6, 4], [11.5, 11.6, 4]]}
    flops = costs.forward_flops(SMALL, 2, 8, logit_rows=2) \
        + costs.forward_flops(SMALL, 4, 10, logit_rows=1)
    assert readers.serve_mfu_pct(obs) == pytest.approx(
        100.0 * flops / 197e12)
    obs["steps"] = [[10.2, 10.4]]
    assert readers.train_mfu_pct(obs) == pytest.approx(
        100.0 * costs.train_flops(SMALL, 2, 4) / 197e12)
    obs["steps"] = []
    assert readers.train_mfu_pct(obs) is None


def test_percentile_and_rates():
    requests = [{"due": 0.0, "sent": 0.0, "done": 0.1 * (i + 1), "ok": True,
                 "tokens": [1] * 5, "t_prefill": 0.05 * (i + 1),
                 "t_done": 0.1 * (i + 1)} for i in range(20)]
    requests[3]["ok"] = False          # a failure ranks last
    obs = {"requests": requests, "window": [0.0, 1.55], "seconds": 1.55}
    # 19 good waits 0.1..2.0 without 0.4, one at t_end + 60: the 19th of 20
    assert readers.request_p95_s(obs) == pytest.approx(2.0)
    # answered inside the window and ok: 15 - 1 requests of 5 tokens; the
    # five still decoding at the close began at 0.8 .. 1.0 and end at
    # 1.6 .. 2.0: their tokens count by the share of that span inside
    shares = [(1.55 - 0.05 * n) / (0.05 * n) for n in range(16, 21)]
    assert readers.serve_tokens_per_s(obs) == pytest.approx(
        (14 * 5 + 5 * sum(shares)) / 1.55)
    # a request whose prefill had not begun at the close counts nothing
    requests[19]["t_prefill"] = 1.6
    assert readers.serve_tokens_per_s(obs) == pytest.approx(
        (14 * 5 + 5 * sum(shares[:-1])) / 1.55)


def test_train_rate_counts_the_step_under_way_at_the_close():
    obs = {"window": [10.0, 13.0], "seconds": 3.0,
           "run": {"traffic": {"batch": 2, "seq": 4}},
           "reports": [[9.0, "check", 0, 1.0], [10.0, "window", 1, 0.0],
                       [11.0, "run", 1, 1.0], [12.0, "run", 2, 1.0],
                       [13.5, "run", 3, 1.0]]}
    # two whole steps and two thirds of the third (12.0 -> 13.5, cut at 13)
    assert readers.train_tokens_per_s(obs) == pytest.approx(
        (2 + 2 / 3) * 8 / 3.0)
    obs["reports"].pop()               # no report after the close
    assert readers.train_tokens_per_s(obs) == pytest.approx(2 * 8 / 3.0)


# ------------------------------------- the reference and its control

TINY = {"model_type": "ouro", "head_dim": 16, "hidden_act": "silu",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": True, "total_ut_steps": 1,
        "use_sliding_window": False, "vocab_size": 512,
        "torch_dtype": "float32"}


def test_the_reference_agrees_with_the_program_and_its_control_does_not(
        ouro):
    """Serving, at a size a test can hold: the program's own greedy
    decode lies within rounding of the reference's best logit at every
    token; the int8 control does not, and neither does an altered
    token. (At this width the seed's 0.02 initializer leaves the
    residual stream to the embedding alone, and every model then
    repeats its input token; the layers' matrices are scaled up so that
    they decide the logits, as they do at the published width.)"""
    import jax.numpy as jnp

    from benchmarks import reference
    from ray_tpu.models import decode

    family = loader.family_module(ouro, "reference")
    sz = family.sizes_of(TINY)
    params = family.seeded_params(2**31 + 3, sz)
    params["layers"] = {k: v * 8 if v.ndim == 3 else v
                        for k, v in params["layers"].items()}
    cfg = loader.family_module(ouro, "program").program_config(TINY, 256)
    prompt = traffic.prompt_tokens(1, 0, 16, sz.vocab)
    served = decode.generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                             steps=200, max_len=256)[0].tolist()
    gaps = reference.served_logit_gaps(family, params, prompt, served, sz,
                                       quant="int8")
    assert len(gaps["served"]) == len(gaps["control"]) == 200
    limit = 1e-3
    assert max(gaps["served"]) < limit / 3
    assert max(gaps["control"]) > 3 * limit
    wrong = list(served)
    wrong[5] = (wrong[5] + 1) % sz.vocab
    assert max(reference.served_logit_gaps(
        family, params, prompt, wrong, sz)["served"]) > 3 * limit


def test_the_training_control_and_faults_fail_a_number(ouro):
    from benchmarks import reference

    family = loader.family_module(ouro, "reference")
    sz = family.sizes_of(TINY)
    batch_of = lambda i: traffic.train_batch(5, i, 4, 32, sz.vocab)  # noqa
    want = reference.train_reference(family, 5, sz, batch_of)
    assert want["losses"][0] == pytest.approx(6.24, abs=0.1)   # ln 512
    same = reference.compare_training(want, want)
    assert max(same.values()) == 0.0
    first = reference.train_reference(
        family, 5, sz, batch_of, steps=1,
        keep_first_gradient=True)["first_gradient"]

    def read(**fault):
        got = reference.train_reference(family, 5, sz, batch_of,
                                        other_first_gradient=first, **fault)
        return reference.compare_training(got, want, got["grad_diff_norms"])

    # the reference against itself: only the bfloat16 the gradient is
    # kept in between the two readings
    assert read()["grad_diff_gap"] < 3e-3
    # the control turns the gradient and leaves its norms nearly alone
    control = read(quant="int8")
    assert control["grad_diff_gap"] > 1e-2 > control["grad_norm_gap"] > 1e-3
    assert read(quant="fp8")["grad_diff_gap"] > 3 * control["grad_diff_gap"]
    half = read(rows=[0, 1])
    assert half["grad_norm_gap"] > 0.1 and half["grad_diff_gap"] > 0.5
    assert read(frozen=True)["change_norm_gap"] == pytest.approx(1.0)


def test_what_the_block_cannot_express_is_refused(ouro):
    reference = loader.family_module(ouro, "reference")
    for key, value in (("num_key_value_heads", 2), ("total_ut_steps", 4),
                       ("tie_word_embeddings", False),
                       ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match="cannot express"):
            reference.sizes_of(dict(TINY, **{key: value}))


# --------------------------------------------------- no chip, no result

def test_fewer_chips_or_another_platform_give_no_result():
    from benchmarks import run

    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    run.require_device(tpu, "tpu", 1)
    for seen, chips in ((tpu, 4), (dict(tpu, platform="cpu"), 1)):
        with pytest.raises(loader.BenchmarkError, match="jax found"):
            run.require_device(seen, "tpu", chips)


def test_a_directory_without_the_program_gives_no_result(
        monkeypatch, capsys, tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files a run exits non-zero and prints no result line."""
    from benchmarks import run

    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "run_cell", lambda *a, **k: pytest.fail(
        "a run was started"))
    code = run.main(["--workload", "ouro-2.6b.decode-closed", "--seed",
                     str(2**31 + 5), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0 and out.out == "" and "NO RESULT" in out.err


# ------------------------------------------- set-up: libtpu's host buffer

@pytest.mark.parametrize("asked,bytes_", [(None, 512 << 20),
                                          (1 << 30, 1 << 30)])
def test_a_session_starts_libtpu_with_the_cells_host_buffer(
        monkeypatch, asked, bytes_):
    """The worker that holds the chip inherits the session's
    environment: the harness's 512 MiB, or what the mix asks for."""
    import ray_tpu
    from benchmarks import run

    monkeypatch.setenv("TPU_PREMAPPED_BUFFER_SIZE", "1")
    for name in ("PYTHONPATH", "RAY_TPU_TMPDIR"):   # session() sets them
        monkeypatch.setenv(name, os.environ.get(name, ""))
    monkeypatch.setattr(ray_tpu, "init", lambda **kw: {"session_dir": ""})
    monkeypatch.setattr(ray_tpu, "shutdown", lambda: None)
    monkeypatch.setattr(run, "wait_until_ended", lambda started: None)
    monkeypatch.setattr(run, "adopt_orphans", lambda: None)
    with run.session(1, asked):
        assert os.environ["TPU_PREMAPPED_BUFFER_SIZE"] == str(bytes_)


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_the_batch_program_does_not_hold_the_seed(seed):
    """One program for every seed (else each new seed compiles it anew
    in set-up), and the batches the seed itself gives."""
    import functools

    import jax
    import numpy as np

    from benchmarks import reference

    sizes = dict(batch=4, seq=32, vocab=512)
    program = jax.jit(functools.partial(traffic.train_batch, **sizes))
    texts = {program.lower(reference.seed_key(s), 3).as_text()
             for s in (seed, 11)}
    assert len(texts) == 1
    got = traffic.batch_maker(seed, **sizes)(3)
    want = traffic.train_batch(seed, 3, **sizes)
    assert all(np.array_equal(got[k], want[k]) for k in want)
    assert not np.array_equal(got["tokens"][0], got["tokens"][1])
