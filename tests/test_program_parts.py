"""A compiled decode step, part by part: ``transformer.PARTS`` names the
parts of the block, ``decode.program_parts`` reads the compiled text's
``op_name`` metadata into ``{instruction: [run, part]}``,
``JaxSlotEngine``'s first step makes that table once from the executable
it runs (no compile, no cache load) and ``parts()`` hands it out,
``DecodeScheduler.stats()`` takes it along and ``Replica.stats()`` leaves
it behind. On the CPU at tiny
widths, one configuration of each kind the benchmark's cells serve; and
for the described ``v5e:2x2`` each serving cell's own decode step
(skipped where that chip cannot be described).
"""

import asyncio
import importlib
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import decode, init_params
from ray_tpu.models.transformer import (LAYER_WEIGHTS, PARTS,
                                        TransformerConfig, layer_runs)
from ray_tpu.serve.decode_scheduler import (DECODE_PROGRAM, DecodeScheduler,
                                            JaxSlotEngine)
from ray_tpu.serve.replica import Replica

F, W, M, D, E = "full", "window", "mamba", "dense", "experts"
TINY = {
    "dense": dict(vocab=64, d_model=32, n_heads=4, n_layers=3, d_ff=64,
                  max_seq=32, dtype=jnp.float32),
    "window-full-experts": dict(
        vocab=64, d_model=32, n_heads=4, n_layers=5, d_ff=64, max_seq=32,
        dtype=jnp.float32, tie_embeddings=False, n_kv_heads=1,
        qk_head_dim=12, v_head_dim=8, rotary_dim=4, value_scale=0.707,
        layer_kinds=((F, D), (W, E), (W, E), (F, E), (W, E)), window=8,
        window_kv_heads=2, sink_kinds=(W,), n_experts=16,
        experts_per_token=2, experts_first=4, experts_held=4, d_expert=16),
    "mamba-attention": dict(
        vocab=64, d_model=32, n_heads=4, n_layers=4, d_ff=64, max_seq=32,
        dtype=jnp.float32, rope=False,
        layer_kinds=((M, D), (M, D), (F, D), (M, D)), ssm_inner=64,
        ssm_state=8, ssm_dt_rank=4, ssm_conv=4),
}
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@pytest.fixture(scope="module", params=sorted(TINY))
def stepped(request):
    """(cfg, an engine that has prefilled a slot and run a step, the
    compiled text of that step)."""
    cfg = TransformerConfig(**TINY[request.param])
    engine = JaxSlotEngine(init_params(jax.random.key(0), cfg), cfg,
                           slots=2, max_len=32)
    assert engine.parts() is None       # nothing has compiled yet
    engine.step({0: engine.prefill(0, [1, 2, 3, 4, 5])})
    text = decode.slot_decode_step.lower(
        engine._params, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), engine._cache),
        jax.ShapeDtypeStruct((2,), jnp.int32), None, cfg).compile().as_text()
    return cfg, engine, text


# ------------------------------------------------------- the table itself

def test_every_listed_instruction_lies_in_a_part_or_outside_every_run(
        stepped):
    cfg, engine, _ = stepped
    table = engine.parts()
    runs = {f"run{r}" for r in range(len(layer_runs(cfg)))}
    assert len(table) > 20
    for name, (run, part) in table.items():
        assert run is None or run in runs, name
        if part == LAYER_WEIGHTS:
            assert run is not None, name
        elif part is None:
            assert run is None, name
        else:
            assert part in PARTS, (name, part)
    # every part the layers' kinds imply names an instruction of its
    # own at these widths, and every run some
    parts = {part for _, part in table.values()}
    assert set(decode.decode_parts(cfg)) - runs <= parts
    assert {run for run, _ in table.values()} >= runs
    # of the parts a decode step never runs, none
    assert "ssm_scan" not in parts


def test_no_container_and_nothing_fused_is_listed(stepped):
    _, engine, text = stepped
    table = engine.parts()
    opcode = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(", text, re.M)}
    assert not {opcode[name] for name in table} & {
        "while", "conditional", "call", "parameter", "tuple",
        "get-tuple-element", "constant", "bitcast"}
    assert "while" in opcode.values()       # the layers' scan is there
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    inside = set()
    for comp, body in re.findall(
            r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S):
        if comp in fused:
            inside |= set(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", body,
                                     re.M))
    assert inside and not inside & set(table)


def test_the_innermost_part_names_a_decode_steps_recurrence(stepped):
    cfg, engine, _ = stepped
    if not cfg.has_mamba:
        pytest.skip("no recurrence in this configuration")
    by_part = {}
    for name, (run, part) in engine.parts().items():
        by_part.setdefault(part, set()).add(run)
    # the three Mamba runs each have both, the attention run neither
    assert by_part["ssm_step"] == by_part["mamba_mixer"] == {
        "run0", "run2"}
    assert by_part["full_attention"] == {"run1", None} or \
        by_part["full_attention"] == {"run1"}


def test_a_text_with_a_part_stripped_out_gives_none(stepped):
    cfg, _, text = stepped
    want = decode.decode_parts(cfg)
    assert decode.program_parts(text, want) is not None
    for gone in ("head", "run0", want[0]):
        stale = re.sub(rf"(?<=[/\"(]){gone}(?=[/\")])", "elsewhere", text)
        assert stale != text
        assert decode.program_parts(stale, want) is None, gone
        # without what to expect, the text is read as it is
        assert decode.program_parts(stale) is not None


# ------------------------------------------------------ a hand-built text

HAND = '''HloModule jit_step, is_scheduled=true

%fused_norm (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/run1/while/body/closed_call/qkv/mul"}
}

%fused_two_parts (p1: f32[4]) -> f32[4] {
  %p1 = f32[4]{0} parameter(0)
  %n = f32[4]{0} negate(%p1), metadata={op_name="jit(step)/run1/while/body/closed_call/mlp/neg"}
  ROOT %sum = f32[4]{0} add(%n, %n), metadata={op_name="jit(step)/run1/while/body/closed_call/attn_out/add"}
}

%inner_body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %g = f32[4]{0} get-tuple-element(%t), index=1
  %copy.9 = f32[4]{0:T(128)S(1)} copy(%g)
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%g, %copy.9)
}

%inner_cond (t2: (s32[], f32[4])) -> pred[] {
  %t2 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%t2, %t2), direction=LT
}

%body (c: (s32[], f32[4])) -> (s32[], f32[4]) {
  %c = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%c), index=1
  %slice_fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_norm, metadata={op_name="jit(step)/run1/while/body/dynamic_slice"}
  %copy.1 = f32[4]{0:T(128)} copy(%slice_fusion.1)
  %fusion.7 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused_norm, metadata={op_name="jit(step)/run1/while/body/closed_call/mamba_mixer/ssm_step/mul"}
  %fusion.8 = f32[4]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_two_parts, metadata={op_name="jit(step)/run1/while/body/closed_call/attn_out/add"}
  %while.3 = (s32[], f32[4]{0}) while(%c), condition=%inner_cond, body=%inner_body, metadata={op_name="jit(step)/run1/while/body/closed_call/experts/while"}
  %kernel.5 = f32[4]{0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/run1/while/body/closed_call/full_attention/pallas_call"}
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%x, %kernel.5)
}

%cond (c2: (s32[], f32[4])) -> pred[] {
  %c2 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.2 = pred[] compare(%c2, %c2), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %constant.1 = s32[] constant(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_norm, metadata={op_name="jit(step)/embed/gather"}
  %copy-start.1 = (f32[4]{0:S(1)}, f32[4]{0}, u32[]{:S(2)}) copy-start(%a)
  %copy-done.1 = f32[4]{0:S(1)} copy-done(%copy-start.1)
  %tuple.0 = (s32[], f32[4]{0}) tuple(%constant.1, %fusion.1)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(step)/run1/while"}
  %y = f32[4]{0} get-tuple-element(%while.1), index=1
  %hoisted.2 = f32[4]{0} fusion(%y), kind=kLoop, calls=%fused_norm, metadata={op_name="jit(step)/run1/while/body/closed_call/router/dot_general"}
  ROOT %fusion.9 = f32[4]{0} fusion(%hoisted.2), kind=kLoop, calls=%fused_norm, metadata={op_name="jit(step)/head/argmax"}
}
'''


def test_a_hand_built_text_reads_as_worked_by_hand():
    assert decode.program_parts(HAND) == {
        "fusion.1": [None, "embed"],
        # a copy of a parameter has nothing to take a scope from
        "copy-start.1": [None, None], "copy-done.1": [None, None],
        # in the run and in no part: the weights' slice, and the copy
        # the compiler hung on it, which has no metadata of its own
        "slice_fusion.1": ["run1", LAYER_WEIGHTS],
        "copy.1": ["run1", LAYER_WEIGHTS],
        # the innermost part wins
        "fusion.7": ["run1", "ssm_step"],
        # a fusion lies where its own metadata puts it, which is its
        # root's, whatever else it fused
        "fusion.8": ["run1", "attn_out"],
        # the experts' own loop: its body's bare copy and its condition
        # take the part the while names; the while itself is not listed
        "copy.9": ["run1", "experts"], "lt.1": ["run1", "experts"],
        "kernel.5": ["run1", "full_attention"],
        # the layers' loop condition: in the run, in no part
        "lt.2": ["run1", LAYER_WEIGHTS],
        # hoisted out of the loop, it keeps the scope it was traced in
        "hoisted.2": ["run1", "router"],
        "fusion.9": [None, "head"]}
    assert decode.program_parts(HAND, ["embed", "run1", "qkv"]) is not None
    assert decode.program_parts(HAND, ["embed", "run0"]) is None
    assert decode.program_parts(HAND, ["window_attention"]) is None


@pytest.mark.parametrize("op_name,want", [
    ("jit(slot_decode_step)/run0/while/body/closed_call/mlp/dot_general",
     ("run0", "mlp")),
    ("jit(slot_decode_step)/run12/while/body/closed_call/mamba_mixer/"
     "ssm_step/mul", ("run12", "ssm_step")),
    ("jit(slot_decode_step)/run2/while/body/dynamic_slice", ("run2", None)),
    ("jit(slot_decode_step)/head/jit(_where)/select_n", (None, "head")),
    ("jit(slot_decode_step)/run0/while/body/closed_call/qkv/jit(rope)/mul",
     ("run0", "qkv")),
    ("params['layers'][0]['wk']", (None, None)),
    ("jit(f)/runner/unembedded/mlps", (None, None)),
    ("", (None, None))])
def test_an_op_names_scope_is_its_run_and_its_innermost_part(op_name, want):
    assert decode._scope_of(op_name) == want


# ------------------------------------- the engine, scheduler and replica

def prefilled(cfg, seed=1):
    engine = JaxSlotEngine(init_params(jax.random.key(seed), cfg), cfg,
                           slots=3, max_len=32)
    return engine, engine.prefill(1, [3, 1, 4, 1, 5, 9])


def test_the_engines_table_costs_no_compile_and_is_made_once():
    cfg = TransformerConfig(**TINY["window-full-experts"])
    warm, first = prefilled(cfg)
    warm.step({1: first})       # the programs compile here
    engine, first = prefilled(cfg)
    assert engine.parts() is None
    seen, armed = [], [True]

    def on_event(name, _secs, **_kw):   # a listener cannot be taken back
        if armed[0]:
            seen.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        # the engine's first step makes its table, from the executable
        # that the other engine's step compiled
        out = engine.step({1: first})
        table = engine.parts()
        again = engine.parts()
    finally:
        armed[0] = False
    assert out and table and again is table
    assert table == warm.parts()
    assert seen     # the listener hears this step: a jaxpr trace, no more
    assert not [name for name in seen if name in COMPILE_EVENTS], seen
    # and the steps go on from the cache the engine holds, its table kept
    assert engine.step({1: engine._last[1]})
    assert engine.parts() is table


def test_a_table_that_cannot_be_read_fails_no_step_and_is_asked_for_again(
        monkeypatch):
    cfg = TransformerConfig(**TINY["dense"])
    engine, first = prefilled(cfg, seed=2)
    real, asked = decode.program_parts, []

    def unreadable(text, expect=()):
        asked.append(len(text))
        raise ValueError("no such text")

    monkeypatch.setattr(decode, "program_parts", unreadable)
    assert engine.step({1: first})              # the step is answered
    assert engine.parts() is None and len(asked) == 1
    assert engine.step({1: engine._last[1]})
    assert len(asked) == 1      # not every step: the cache's first alone
    # a stale executable's text reads as None, and that is no table
    monkeypatch.setattr(decode, "program_parts", lambda text, expect=(): None)
    engine._start_over()
    engine.step({1: engine.prefill(1, [2, 7, 1])})
    assert engine.parts() is None
    monkeypatch.setattr(decode, "program_parts", real)
    engine._start_over()
    engine.step({1: engine.prefill(1, [2, 7, 1])})
    assert engine.parts() and "parts" in DecodeScheduler(engine).stats()


class Wrapped:
    """A timing wrapper, as the benchmark's: the engine is ``inner``."""

    def __init__(self, inner):
        self.inner, self.slots = inner, inner.slots


class FakeEngine:
    slots = 2

    def prefill(self, slot, prompt):
        return 0

    def step(self, tokens):
        return dict.fromkeys(tokens, 1)


def test_the_schedulers_stats_carry_the_table_of_an_engine_that_has_one(
        stepped):
    _, engine, _ = stepped
    for offered in (engine, Wrapped(engine), Wrapped(Wrapped(engine))):
        got = DecodeScheduler(offered).stats()
        assert got["parts"] == {DECODE_PROGRAM: engine.parts()}
        assert got["parts"][DECODE_PROGRAM] is engine.parts()
    for bare in (FakeEngine(), Wrapped(FakeEngine())):
        assert "parts" not in DecodeScheduler(bare).stats()
    # before an engine's first step there is a key and no table yet
    cfg = TransformerConfig(**TINY["dense"])
    fresh = JaxSlotEngine(init_params(jax.random.key(0), cfg), cfg,
                          slots=2, max_len=32)
    assert DecodeScheduler(fresh).stats()["parts"] == {DECODE_PROGRAM: None}


def test_a_replicas_stats_leave_the_table_behind(stepped):
    _, engine, _ = stepped

    class HostsADecodeLoop:
        def __init__(self):
            self.decode_scheduler = DecodeScheduler(engine)

    async def polled():
        replica = Replica(HostsADecodeLoop, (), {})
        assert "parts" in replica._obj.decode_scheduler.stats()
        return await replica.stats()

    got = asyncio.run(polled())
    assert "parts" not in got["decode"]
    assert {"steps", "slot_steps", "phases", "queue_depth"} <= set(
        got["decode"])


# ------------------- each serving cell's decode step, for the described chip

CELLS = ("ouro-2.6b.decode-closed", "mimo-v2-flash-ep16-d7.reason-closed",
         "jamba2-3b.rollout-closed")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_compiled_decode_step_names_a_run_in_every_loop_body(
        cell, one_chip, no_compile_cache, monkeypatch):
    from benchmarks import loader

    for module in ("ray_tpu.ops.attention", "ray_tpu.ops.ssm",
                   "ray_tpu.parallel.experts"):
        monkeypatch.setattr(importlib.import_module(module), "_on_tpu",
                            lambda: True)
    bench = loader.load_benchmark()
    entry = loader.find_cell(bench, cell)
    config = loader.load_config(bench, entry["config"])
    mix = loader.load_traffic(bench, entry["traffic"])
    program = loader.family_module(loader.find_family(bench, config),
                                   "program")
    cfg = program.program_config(config, mix["slot_len"])
    slots = int(mix["slots"])

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    text = decode.slot_decode_step.lower(
        described(jax.eval_shape(
            lambda: init_params(jax.random.key(0), cfg))),
        described(jax.eval_shape(lambda: decode.init_slot_cache(
            cfg, slots, int(mix["slot_len"])))),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
        None, cfg).compile().as_text()
    table = decode.program_parts(text, decode.decode_parts(cfg))
    assert table is not None
    # every instruction of a loop body that the table lists has a run
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    assert bodies
    in_a_body = set()
    for comp, body in re.findall(
            r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S):
        if comp in bodies:
            in_a_body |= set(re.findall(
                r"^\s*(?:ROOT )?%?([\w.\-]+) = ", body, re.M))
    listed = in_a_body & set(table)
    assert len(listed) > 10
    assert not [name for name in listed if table[name][0] is None]
    # the kernel lies where the callers' attend puts it, and every run
    # of full-attention layers has one
    kernels = {name: where for name, where in table.items()
               if re.sub(r"\.\d+$", "", name) == "decode_attend"}
    full_runs = [f"run{r}" for r, ((mixer, _), _) in
                 enumerate(layer_runs(cfg)) if mixer == F]
    assert sorted(run for run, _ in kernels.values()) == full_runs
    assert {part for _, part in kernels.values()} == {"full_attention"}
    # the table is small enough to ride a stats call: some hundred
    # entries, some ten kilobytes
    assert len(table) < 2000
