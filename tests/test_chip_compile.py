"""The flash kernels at the shapes the benchmark's cells run, compiled
for a TPU v5e that is described and not attached (libtpu's compiler runs
on this CPU box; nothing executes): Mosaic accepts them at the blocks
``flash_blocks`` chooses (a table that overflows VMEM fails here), every
shape takes the kernel (a prompt of 128 too), and the compiled program
names its three custom calls ``flash_fwd``, ``flash_bwd_dkv`` and
``flash_bwd_dq``, which is what the benchmark's kernel readers look up
in a device trace.

The serving cell's two programs are compiled the same way, at the
cell's own shapes, and held to what keeps the slot cache one buffer:
the cache aliased to the result, no temporary the size of a layer's K,
and no operation that produces K or V but the in-place writes. A
decode step names one Mosaic call ``decode_attend`` a run of
full-attention layers and one ``decode_ring`` a run of window layers,
which copies each row of the carried cache or ring itself, where it
lies, up to the row's position; that kernel alone is compiled at each
attending cell's decode shape and at each window cell's ring, at the
chunk ``decode_chunks`` gives it.

The second serving cell's programs (``mimo-v2-flash-ep16-d7``: layers of
several kinds, a cache allocated by kind, a chip's share of the
experts) are held to the same, at that cell's shapes; its decode step
holds one ``expert_ffn`` call a run of expert layers, in the part
``experts``, and nothing outside a fusion slices or copies an expert's
matrix (its prefills keep the XLA tile loop). Both cells'
decode steps hand the host a row of picked tokens and nothing of
``[slots, vocab]``; the serving programs of both cells and Ouro's train
step lower to the text on record.

The third serving cell's programs (``jamba2-3b``: Mamba layers beside
attention layers, a slot's recurrent state beside its K/V) are held to
the same at that cell's shapes: every leaf of the cache aliased, the
recurrent state read and written where it lies with no copy of a run's
or a layer's state, each prefill holding both kernels (``ssm_scan`` a
Mamba run, ``flash_fwd`` an attention run), a decode step one
``ssm_step`` call a Mamba run, whose operand and result the run's
whole state is, and both kernels alone accepted by Mosaic: the scan at
the cell's three prompt lengths, the step at its 256 rows.

The fourth serving cell's programs (``brumby-14b-d8``: every mixer a
power-retention layer, a matrix state a K/V head and no K/V at all)
are held to the same at that cell's shapes: the cache has no key and no
value leaf, its state leaves are aliased to the result, no operation
yields an array of the run's or a layer's state but the in-place writes
and the step's own kernel, the decode step's temporaries stay in the
tens of MB, a decode step names one Mosaic call ``retention_step`` and
a prefill one ``retention_chunk``, and both kernels alone are accepted
by Mosaic at the cell's widths.

The fifth serving cell's programs (``phi-4-mini-flash``: runs of
periods of two layers, one full-attention layer whose cache seven cross
layers read, gated memory units, a prefill in two stages) are held to
the same at that cell's shapes: every leaf of the cache aliased, the
one growing cache produced by nothing but its own layer's in-place
write (the cross run takes the full run's arrays as they lie: no copy,
no slice of a layer), a decode step two ``decode_attend`` calls (the
full layer's, and one in the cross run's loop), one ``decode_ring``
(the window run's) and two ``ssm_step``, a
prefill one ``flash_fwd`` and two ``ssm_scan`` (the full layer's one
query, the prompt's last, needs no kernel).

Every serving cell's decode step is also held to reading each layer's
``wq``, ``wk`` and ``wv`` where they lie in the stack: no slice of a
layer's matrix into fast memory as an operation of its own and no
relaid copy of one, in a run that kept its loop and in one XLA unrolled.

The topology is described inside a fixture, by the one xdist worker
that is given this file: libtpu loads in one process at a time, so no
other test file may do the same and nothing here runs at import.
"""

import functools
import importlib
import math
import re

import pytest

KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
SCAN_KERNEL = "ssm_scan"
STEP_KERNEL = "ssm_step"
DECODE_KERNEL, DECODE_RING = "decode_attend", "decode_ring"
EXPERT_KERNEL = "expert_ffn"
RETENTION_KERNELS = ("retention_chunk", "retention_step")
# B, T, H, Dh, the dtype, and whether the gradient is compiled too
SHAPES = {
    # ouro-2.6b-d12.train-2k
    "train-2k": ((4, 2048, 16, 128), "bfloat16", True),
    # ouro-2.6b.decode-closed
    "prefill-128": ((1, 128, 16, 128), "bfloat16", False),
    "prefill-256": ((1, 256, 16, 128), "bfloat16", False),
    # chip_smoke.py: its train leg, and the longer of its kernel checks
    "dense-168m": ((16, 1024, 16, 64), "bfloat16", True),
    "smoke-4k": ((4, 4096, 8, 128), "bfloat16", True),
    # rows of 512 bytes, two ways, which keep the caps swept at 256, and
    # of 1024, which halve them: each has to fit VMEM as well
    "train-2k-f32": ((4, 2048, 16, 128), "float32", True),
    "head-256": ((2, 2048, 8, 256), "bfloat16", True),
    "head-256-f32": ((2, 2048, 8, 256), "float32", True),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def mosaic_calls(compiled_text):
    """Which kernel each Mosaic custom call of the program is, by the
    instruction's name as the benchmark's kernel readers match it."""
    from benchmarks.inside import kernel_of

    names = re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled_text)
    return sorted(kernel_of(n, KERNELS + (SCAN_KERNEL, STEP_KERNEL,
                                          DECODE_KERNEL, DECODE_RING,
                                          EXPERT_KERNEL)
                            + RETENTION_KERNELS) or n for n in names)


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_flash_kernels_compile_for_v5e_under_their_own_names(
        cell, one_chip, no_compile_cache, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.models import transformer

    # (ray_tpu.ops exports a function under the module's name)
    attention = importlib.import_module("ray_tpu.ops.attention")

    # this process's default backend is the CPU; the program is for the
    # described chip, so take the branch a TPU process takes
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    shape, dtype, with_gradient = SHAPES[cell]
    arg = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

    def loss(q, k, v):
        # as models/transformer.py runs its layers under remat: a scan
        # over bodies checkpointed under the module's own rule, so that
        # the kernels get the names the training program gives them
        # (under a bare jax.grad the compiler wraps them:
        # jvp_flash_fwd_)
        layer = jax.checkpoint(
            lambda h: attention.flash_attention(h, k, v, causal=True),
            policy=transformer.KEPT)
        out, _ = lax.scan(lambda h, _: (layer(h), None), q, None, length=2)
        return jnp.sum(out.astype(jnp.float32))

    forward = jax.jit(attention.flash_attention).lower(
        arg, arg, arg).compile().as_text()
    assert "%flash_fwd.1 = " in forward
    assert mosaic_calls(forward) == ["flash_fwd"]
    if not with_gradient:
        return
    both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        arg, arg, arg).compile().as_text()
    # the forward and the backward's two: the rule keeps what the
    # backward kernels read, so the backward runs no second forward
    assert mosaic_calls(both) == sorted(KERNELS)


# the fixture of each serving cell whose decode step attends, and the
# chunk of positions that ``decode_chunks`` gives its full-attention
# runs' cache
DECODE_CHUNKS = {"serving_cell": 128, "kinds_cell": 128, "hybrid_cell": 512,
                 "shared_cell": 128}


@pytest.mark.parametrize("cell", sorted(DECODE_CHUNKS))
def test_the_decode_kernel_compiles_for_v5e_under_its_own_name(
        cell, request, one_chip, no_compile_cache, monkeypatch):
    """``decode_attend`` alone at an attending cell's decode shape, the
    first full-attention run of the cache the cell allocates (rows as
    it holds them: [H, Dh] a position for Ouro, flat where K/V heads
    are shared), at the chunk ``decode_chunks`` chooses for it: Mosaic
    accepts it (buffers that overflow the scoped VMEM fail here), the
    run's K and V reach the call as they lie (no operation produces an
    array of their shape, no temporary has room for one), and the
    program names its one custom call ``decode_attend``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode
    from ray_tpu.models.transformer import layer_runs

    attention = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg, slots, slot_len, _ = request.getfixturevalue(cell)
    cache = jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, slot_len))
    assert decode.kv_rows_fetched(cfg, cache) == DECODE_CHUNKS[cell]
    runs = layer_runs(cfg)
    mixer, full = decode._grown(runs, decode._cache_runs(cache, runs))
    assert mixer == "full"

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    k, v = (array(t.shape, cfg.dtype) for t in full)
    # (differential attention's heads go in pairs, each query twice as
    # wide: ``transformer._paired``)
    q = array((slots, cfg.n_heads, cfg.head_dim * (
        2 if cfg.differential else 1)), cfg.dtype)
    compiled = jax.jit(attention.decode_attention).lower(
        q, k, v, array((), jnp.int32), array((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "%decode_attend.1 = " in text
    assert mosaic_calls(text) == [DECODE_KERNEL]
    assert not cache_producers(text, {k.shape, v.shape, k.shape[1:],
                                      v.shape[1:]})
    # the queries (widened to a flat row of G heads, or padded to whole
    # tiles) and the output, and nothing of the cache's size
    G = cfg.kv_heads("full")
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 8 * math.prod(q.shape) * (1 if G == cfg.n_heads else G)


# the fixture of each serving cell with window layers, and whether they
# carry a sink
RING_CELLS = {"kinds_cell": True, "shared_cell": False}


@pytest.mark.parametrize("cell", sorted(RING_CELLS))
def test_the_ring_kernel_compiles_for_v5e_under_its_own_name(
        cell, request, one_chip, no_compile_cache, monkeypatch):
    """``decode_ring`` alone at a window cell's decode shape, the first
    window run's rings as the cell allocates them (with the layers' sink
    where they have one), at the chunk ``decode_chunks`` chooses: Mosaic
    accepts it, the rings reach the call as they lie, and the program
    names its one custom call ``decode_ring``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode
    from ray_tpu.models.transformer import layer_runs

    attention = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg, slots, slot_len, _ = request.getfixturevalue(cell)
    cache = jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, slot_len))
    runs = layer_runs(cfg)
    ring = next(pair for (kind, _), state in zip(
        runs, decode._cache_runs(cache, runs))
        for (mixer, _), pair in zip(decode.period_of(kind), state)
        if mixer == "window")
    assert ring[0].shape[2] == cfg.window

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    k, v = (array(t.shape, cfg.dtype) for t in ring)
    q = array((slots, cfg.n_heads, cfg.head_dim * (
        2 if cfg.differential else 1)), cfg.dtype)
    sink = array((cfg.n_heads,), jnp.float32) if RING_CELLS[cell] else None
    assert attention.decode_rows_fetched(q, k, v) == 128
    compiled = jax.jit(functools.partial(
        attention.decode_attention, ring=True)).lower(
            q, k, v, array((), jnp.int32), array((slots,), jnp.int32),
            sink=sink).compile()
    text = compiled.as_text()
    assert "%decode_ring.1 = " in text
    assert mosaic_calls(text) == [DECODE_RING]
    assert not cache_producers(text, {k.shape, v.shape, k.shape[1:],
                                      v.shape[1:]})


# ------------------------------------- the slot cache, written in place

SERVING_CELL = "ouro-2.6b.decode-closed"
INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\](?:\{[^}]*\})? "
    r"([\w\-]+)\((.*)$")
IN_PLACE = ("scatter", "dynamic-update-slice")


def computations(compiled_text):
    """{computation: [(is_root, name, shape, opcode, rest)]} of the
    instructions with an array result (a tuple's holds no new array)."""
    out, current = {}, None
    for line in compiled_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = out.setdefault(head.group(1), [])
        elif current is not None and (m := INSTRUCTION.match(line)):
            root, name, shape, opcode, rest = m.groups()
            current.append((bool(root), name,
                            tuple(int(n) for n in shape.split(",") if n),
                            opcode, rest))
    return out


def outside_fusions(compiled_text):
    """(computation, name, shape, what it does, rest) of the instructions
    outside fused computations: a fusion does what the root of the
    computation it calls does, a custom call what its target says."""
    comps = computations(compiled_text)

    def called(rest):
        return re.search(r"calls=%([\w.\-]+)", rest).group(1)

    root_of = {c: next(op for root, _, _, op, _ in ins if root)
               for c, ins in comps.items() if any(i[0] for i in ins)}
    fused = {called(rest) for ins in comps.values()
             for *_, opcode, rest in ins if opcode == "fusion"}
    for comp, ins in comps.items():
        if comp in fused:
            continue
        for _, name, shape, opcode, rest in ins:
            if opcode == "fusion":
                opcode = root_of[called(rest)]
            elif opcode == "custom-call":
                opcode = re.search(r'custom_call_target="(\w+)"',
                                   rest).group(1)
            yield comp, name, shape, opcode, rest


def cache_producers(compiled_text, shapes):
    """The instructions outside fused computations whose result has one
    of ``shapes``, as (name, what it does): parameters and tuple
    elements name memory and are left out."""
    return [(name, does)
            for _, name, shape, does, _ in outside_fusions(compiled_text)
            if shape in shapes
            and does not in ("parameter", "get-tuple-element")]


# the two cells' decode steps as compiled for the described chip, kept
# for the test that reads them again (a compile each, a minute together)
COMPILED_DECODE = {}


def compiled_decode(cell, cfg, slots, slot_len, one_chip):
    """The served form of ``slot_decode_step`` (steered by one int32
    row, ``active`` None) at a cell's shapes."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode, init_params

    if cell not in COMPILED_DECODE:
        described = lambda tree: jax.tree.map(          # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)
        COMPILED_DECODE[cell] = decode.slot_decode_step.lower(
            described(jax.eval_shape(
                lambda: init_params(jax.random.key(0), cfg))),
            described(jax.eval_shape(
                lambda: decode.init_slot_cache(cfg, slots, slot_len))),
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            None, cfg).compile()
    return COMPILED_DECODE[cell]


@pytest.fixture(scope="module")
def serving_cell():
    """Cell 1 as the benchmark's worker builds it: the program's config
    object, slots and slot length, the prompt lengths."""
    from benchmarks import loader

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, SERVING_CELL)
    config = loader.load_config(bench, cell["config"])
    mix = loader.load_traffic(bench, cell["traffic"])
    program = loader.family_module(loader.find_family(bench, config),
                                   "program")
    return (program.program_config(config, mix["slot_len"]),
            int(mix["slots"]), int(mix["slot_len"]),
            sorted(mix["prompt_lengths"]))


def test_the_serving_cells_shapes_are_the_ones_compiled_here(serving_cell):
    cfg, slots, slot_len, lengths = serving_cell
    assert (cfg.n_layers, slots, slot_len, cfg.n_heads, cfg.head_dim,
            lengths) == (48, 8, 1024, 16, 128, [128, 256])


@pytest.mark.parametrize("program", ["decode", "prefill-128",
                                     "prefill-256"])
def test_the_slot_cache_is_one_buffer_written_in_place(
        program, serving_cell, one_chip, no_compile_cache, monkeypatch):
    """``slot_decode_step`` and ``slot_prefill`` at cell 1's shapes, for
    the described v5e: the cache's K, V, pos and tok are aliased to the
    result (3.22 GB, all of it), the temporaries stay under one layer's
    K (33.5 MB: no copy of a layer, let alone of the cache, has room),
    and the only operations whose result is a layer's or the whole
    cache's K or V are the two in-place writes. Neither half does it
    alone: the parent's program (the cache a scanned input, nothing
    donated) fails every one of these."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode, init_params

    attention = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg, slots, slot_len, _ = serving_cell

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = described(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, slot_len)))
    if program == "decode":
        compiled = compiled_decode(SERVING_CELL, cfg, slots, slot_len,
                                   one_chip)
    else:
        length = int(program.split("-")[1])
        compiled = decode.slot_prefill.lower(
            params, array((1, length), jnp.int32), cache,
            array((), jnp.int32), cfg).compile()
    text = compiled.as_text()

    # the cache's four leaves, and nothing else, alias the result
    # (a model of one kind holds one run: ``cache['k'][0]``)
    parameter = {leaf: int(n) for n, leaf in re.findall(
        r"parameter\((\d+)\)[^\n]*op_name=\"cache\[\\'(\w+)\\'\]"
        r"(?:\[0\])?\"", text)}
    assert sorted(parameter) == ["k", "pos", "tok", "v"]
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliased, "nothing is aliased: the cache is not donated"
    assert sorted(int(n) for n in re.findall(
        r"\((\d+), \{\}, may-alias\)", aliased.group(1))
    ) == sorted(parameter.values())

    layer = (slots, slot_len, cfg.n_heads, cfg.head_dim)
    whole = (cfg.n_layers,) + layer
    itemsize = jnp.dtype(cfg.dtype).itemsize
    layer_bytes = itemsize * slots * slot_len * cfg.n_heads * cfg.head_dim
    memory = compiled.memory_analysis()
    # K and V whole, and pos and tok padded to a tile each
    assert 0 <= (memory.alias_size_in_bytes
                 - 2 * cfg.n_layers * layer_bytes) <= 8192
    assert memory.temp_size_in_bytes < layer_bytes

    produced = cache_producers(text, {layer, whole})
    assert len(produced) == 2, produced         # K's write and V's
    assert {op for _, op in produced} <= set(IN_PLACE), produced
    # the one run's kernel: a prefill's forward, a decode step's
    # attention over the carried cache (its K and V operands are the
    # in-place writes' results under another shape's name: bitcasts)
    assert mosaic_calls(text) == (
        [DECODE_KERNEL] if program == "decode" else ["flash_fwd"])


# --------------------- layers of several kinds, a cache by layer kind

KINDS_CELL = "mimo-v2-flash-ep16-d7.reason-closed"
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def kinds_cell():
    """The cell of the model with window and full layers and sparse
    experts, as the benchmark's worker builds it."""
    from benchmarks import loader

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, KINDS_CELL)
    config = loader.load_config(bench, cell["config"])
    mix = loader.load_traffic(bench, cell["traffic"])
    program = loader.family_module(loader.find_family(bench, config),
                                   "program")
    return (program.program_config(config, mix["slot_len"]),
            int(mix["slots"]), int(mix["slot_len"]),
            sorted(mix["prompt_lengths"]))


def test_the_second_serving_cells_shapes_are_the_ones_compiled_here(
        kinds_cell):
    from ray_tpu.models.transformer import layer_runs

    cfg, slots, slot_len, lengths = kinds_cell
    assert (slots, slot_len, lengths) == (128, 3200, [512, 1024, 2048])
    assert (cfg.n_heads, cfg.head_dim, cfg.v_dim, cfg.rope_dim,
            cfg.kv_heads("full"), cfg.kv_heads("window"), cfg.window,
            cfg.n_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.d_expert, cfg.d_ff, cfg.vocab) == (
        64, 192, 128, 64, 4, 8, 128, 256, 16, 8, 2048, 16384, 152576)
    assert layer_runs(cfg) == (
        (("full", "dense"), 1), (("window", "experts"), 4),
        (("full", "experts"), 1), (("window", "experts"), 1))


@pytest.mark.parametrize("program", ["decode", "prefill-512",
                                     "prefill-1024", "prefill-2048"])
def test_a_cache_by_layer_kind_is_still_written_in_place(
        program, kinds_cell, one_chip, no_compile_cache, as_on_the_tpu):
    """``slot_decode_step`` and ``slot_prefill`` of the model with
    layers of several kinds, at its cell's shapes, for the described
    v5e. Every leaf of the cache is aliased to the result (2.52 GB: the
    full runs' 3200 rows a slot, the window runs' rings of 128); the
    only operations that produce an array of a run's or a layer's K or
    V are the in-place writes; the temporaries stay under the smallest
    full layer's V (0.42 GB: no copy of one has room; the expert
    layers' matrices are indexed where they lie, not sliced a layer at
    a time); the arguments and temporaries fit the chip; and each
    prefill holds its Mosaic calls, one a run of layers."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode, init_params

    cfg, slots, slot_len, _ = kinds_cell

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = described(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, slot_len)))
    # full runs hold slot_len rows a slot, window runs a ring of 128;
    # a row is the layer's K/V heads side by side
    assert [k.shape for k in cache["k"]] == [
        (1, 128, 3200, 768), (4, 128, 128, 1536), (1, 128, 3200, 768),
        (1, 128, 128, 1536)]
    assert [v.shape for v in cache["v"]] == [
        (1, 128, 3200, 512), (4, 128, 128, 1024), (1, 128, 3200, 512),
        (1, 128, 128, 1024)]
    if program == "decode":
        compiled = compiled_decode(KINDS_CELL, cfg, slots, slot_len,
                                   one_chip)
    else:
        length = int(program.split("-")[1])
        compiled = decode.slot_prefill.lower(
            params, array((1, length), jnp.int32), cache,
            array((), jnp.int32), cfg).compile()
    text = compiled.as_text()

    # every leaf of the cache, and nothing else, aliases the result
    leaves = re.findall(
        r"parameter\((\d+)\)[^\n]*op_name=\"cache\[([^\"]*)\]\"", text)
    # K and V of the four runs, pos and tok, and load where the program
    # reads it (a decode step writes its three counts anew)
    assert len(leaves) == 4 + 4 + 2 + (program != "decode")
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliased, "nothing is aliased: the cache is not donated"
    assert sorted(int(n) for n in re.findall(
        r"\((\d+), \{\}, may-alias\)", aliased.group(1))
    ) == sorted(int(n) for n, _ in leaves)

    kv_bytes = sum(2 * math.prod(leaf.shape)
                   for leaf in cache["k"] + cache["v"])
    memory = compiled.memory_analysis()
    assert 0 <= memory.alias_size_in_bytes - kv_bytes <= 8192
    assert memory.temp_size_in_bytes < 2 * 128 * 3200 * 512
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < HBM_BYTES)

    shapes = {leaf.shape for leaf in cache["k"] + cache["v"]} | {
        leaf.shape[1:] for leaf in cache["k"] + cache["v"]}
    # a bitcast names memory; in a prefill a copy-start/copy-done pair
    # is the compiler's prefetch of one window layer's ring (50 MB) into
    # the chip's fast memory, which is that layer's read and no second
    # one; a decode step reads every ring through the kernel, and has
    # neither
    produced = [(name, op) for name, op in cache_producers(text, shapes)
                if op not in ("bitcast",) + (
                    () if program == "decode"
                    else ("copy-start", "copy-done"))]
    assert {op for _, op in produced} <= set(IN_PLACE), produced
    assert len(produced) == 8, produced     # K's and V's write, a run
    # a kernel a run of layers: a prefill's flash forward; in a decode
    # step the two full-attention runs' ``decode_attend`` and the two
    # window runs' ``decode_ring``, each in its run's attention, and the
    # three expert runs' ``expert_ffn`` (a prefill's rows take the loop)
    assert mosaic_calls(text) == (
        sorted([DECODE_KERNEL] * 2 + [DECODE_RING] * 2 + [EXPERT_KERNEL] * 3)
        if program == "decode" else ["flash_fwd"] * 4)
    if program == "decode":
        table = decode.program_parts(text, decode.decode_parts(cfg))
        assert sorted((run, re.sub(r"\.\d+$", "", name), part)
                      for name, (run, part) in table.items()
                      if name.startswith((DECODE_KERNEL, DECODE_RING,
                                          EXPERT_KERNEL))
                      ) == [("run0", DECODE_KERNEL, "full_attention"),
                            ("run1", DECODE_RING, "window_attention"),
                            ("run1", EXPERT_KERNEL, "experts"),
                            ("run2", DECODE_KERNEL, "full_attention"),
                            ("run2", EXPERT_KERNEL, "experts"),
                            ("run3", DECODE_RING, "window_attention"),
                            ("run3", EXPERT_KERNEL, "experts")]
        # the kernel reads each expert's matrices where they lie in the
        # stack: nothing outside a fusion slices or copies one out
        expert = cfg.d_model * cfg.d_expert
        moved = [(name, shape, does)
                 for _, name, shape, does, _ in outside_fusions(text)
                 if math.prod(shape) == expert
                 and does not in ("parameter", "get-tuple-element",
                                  "bitcast")]
        assert not moved, moved


@pytest.mark.parametrize("cell", [SERVING_CELL, KINDS_CELL])
def test_a_decode_step_hands_the_host_a_row_of_picks_and_no_logits(
        cell, serving_cell, kinds_cell, one_chip, no_compile_cache,
        as_on_the_tpu):
    """The served ``slot_decode_step`` of both cells, compiled for the
    described v5e: what it returns beside the cache is one int32 row
    (the picks, with the three expert counts behind them where the
    model has expert layers), a few hundred bytes; the only array of
    ``[slots, vocab]`` in the program is the head's own product, which
    the pick reads where it lies (no second one, no copy, nothing of
    that size among the results); and the whole cache still aliases
    the result."""
    import jax.numpy as jnp

    cfg, slots, slot_len, _ = (serving_cell if cell == SERVING_CELL
                               else kinds_cell)
    compiled = compiled_decode(cell, cfg, slots, slot_len, one_chip)
    text = compiled.as_text()
    row = slots + (3 if cfg.n_experts else 0)
    root = re.search(r"ROOT %[\w.\-]+ = \(([^\n]*?)\) tuple\(",
                     text[text.index("\nENTRY "):]).group(1)
    results = re.findall(r"(\w+)\[([\d,]*)\]", root)
    # the row; beside it the cache's pos and tok are int32 [slots] too
    assert results.count(("s32", str(row))) == (1 if cfg.n_experts else 3)
    assert not [r for r in results
                if r[1] == f"{slots},{cfg.vocab}"], results
    memory = compiled.memory_analysis()
    # beside the aliased cache: the row, padded to a tile
    assert 4 * row <= (memory.output_size_in_bytes
                       - memory.alias_size_in_bytes) <= 4096
    # what lies inside a fusion (of whatever result: the head's product
    # fused with the pick yields a pair of rows) never reaches memory
    fused = set(re.findall(r" fusion\([^\n]*calls=%([\w.\-]+)", text))
    logits = [(comp, name, op)
              for comp, ins in computations(text).items()
              if comp not in fused
              for _, name, shape, op, _ in ins
              if shape == (slots, cfg.vocab)]
    assert len(logits) <= 1, logits
    assert memory.temp_size_in_bytes < 2 * 4 * slots * cfg.vocab + (
        jnp.dtype(cfg.dtype).itemsize * slots * slot_len * cfg.n_heads
        * cfg.head_dim if cell == SERVING_CELL else 2 * 128 * 3200 * 512)


# digests of the lowered programs, made by this file's own normaliser.
# ``train`` was the parent's of before layers had kinds (commit 0b4d871),
# and so were cell 1's serving programs until a step kept its picks on
# the device (``cache["tok"]``, the pick inside both programs, a decode
# step steered by one int32 row and returning the row of picks): those
# three are on record anew, and the second serving cell's four beside
# them; the three cells' ``decode`` once more since a full-attention
# run attends through ``ops.attention.decode_attention`` (every prefill
# and ``train`` as they were); and every serving program of the three
# cells, the prefills too, since ``block`` keeps the flat q, k and v
# products from their reshape to heads where the rows are fewer than the
# weight's (``train``, whose rows are not, and the kernels' jaxprs as
# they were); and ``train`` alone since ``remat=True`` keeps the layers'
# products and what the flash backward kernels read and the step's
# state is donated (every serving program as it was); and the four
# attending cells' ``decode`` since ``decode_attend`` copies each row's
# chunks of K and V itself, from the run's arrays in ``pl.ANY`` (every
# prefill, ``train`` and the fourth serving cell's programs as they
# were); and the two window cells' ``decode`` since a window run's ring
# is the kernel's operand as it lies (``decode_ring``, with the layers'
# sink), where it was read whole by ``cached_attention`` (every other
# program and the kernels' jaxprs as they were); and the second serving
# cell's ``decode`` since its expert layers stream the held experts'
# matrices through one ``expert_ffn`` call a layer, where the tile loop
# read them, and its prefills, whose rows take the loop, since both
# forms count a held expert's rows by one comparison with each expert
# where the loop's scatter-add counted them (every other program as it
# was). Left out: the Mosaic kernels' serialized bodies, which hold the
# line numbers of ops/attention.py, and the results' labels, which name
# the cache's place in the result's tree
TRAIN_CELL = "ouro-2.6b-d12.train-2k"
LOWERED = {
    "decode": "2af5bcf489aafbec",
    "prefill-128": "70d028c10c8b6cff",
    "prefill-256": "a25f52704aaeb6c6",
    "train": "7bc8d8cd2dc6b5c3",
}
LOWERED_KINDS = {
    "decode": "ea139dada34f37f8",
    "prefill-512": "78119910e017573c",
    "prefill-1024": "e7e2298fbbce35a6",
    "prefill-2048": "41a149d7caa1008f",
}
# the forward kernel's as they were before layers had kinds (commit
# 0b4d871); the gradient's since its forward names what the backward
# kernels read (``ops.attention.SAVED``) and hands it over as they read
# it: q, k, v and out heads first, ``lse`` as [B, H, T]
KERNEL_JAXPRS = {"forward": "8cc7f54ad19da989",
                 "gradient": "412db7ef88980e58"}


def without_kernel_bodies(lowered_text):
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", lowered_text)
    return re.sub(r' \{jax\.result_info = "[^"]*"\}', "", text)


def digest(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


def serving_programs_lowered(cell, one_chip):
    """{name: the lowered text's digest} of a serving cell's decode
    step, as served, and of its prefills."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode, init_params

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg, slots, slot_len, lengths = cell
    params = described(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, slot_len)))
    lowered = {"decode": decode.slot_decode_step.lower(
        params, cache, array((slots,), jnp.int32), None, cfg)}
    for length in lengths:
        lowered[f"prefill-{length}"] = decode.slot_prefill.lower(
            params, array((1, length), jnp.int32), cache,
            array((), jnp.int32), cfg)
    return {name: digest(without_kernel_bodies(low.as_text()))
            for name, low in lowered.items()}


def train_step_lowered(one_chip):
    """(the train cell's step lowered for the described chip, as the
    benchmark's worker builds it; the bytes of its params and
    opt_state)."""
    import jax
    import jax.numpy as jnp

    from benchmarks import loader
    from ray_tpu.models import init_params

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, TRAIN_CELL)
    config = loader.load_config(bench, cell["config"])
    mix = loader.load_traffic(bench, cell["traffic"])
    program = loader.family_module(loader.find_family(bench, config),
                                   "program")
    cfg = program.program_config(config, mix["seq"])
    step, optimizer = program.make_train_step(cfg, mix)
    params = described(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    state = (params, described(jax.eval_shape(optimizer.init, params)))
    batch = jax.ShapeDtypeStruct((mix["batch"], mix["seq"]), jnp.int32,
                                 sharding=one_chip)
    return (step.lower(*state, {"tokens": batch, "targets": batch}),
            sum(math.prod(a.shape) * a.dtype.itemsize
                for a in jax.tree.leaves(state)))


def test_the_second_cells_serving_programs_lower_to_the_text_on_record(
        kinds_cell, one_chip, as_on_the_tpu):
    assert serving_programs_lowered(kinds_cell, one_chip) == LOWERED_KINDS


def test_ouros_three_programs_lower_to_the_text_on_record(
        serving_cell, one_chip, monkeypatch):
    """``slot_decode_step`` as served, ``slot_prefill`` (128, 256) and
    the train step of the two Ouro cells lower to the StableHLO on
    record, operation for operation, and the three flash kernels trace
    to the jaxprs on record. A change that means to alter these
    programs records its own digests here."""
    import jax
    import jax.numpy as jnp

    attention = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)

    digests = serving_programs_lowered(serving_cell, one_chip)
    digests["train"] = digest(without_kernel_bodies(
        train_step_lowered(one_chip)[0].as_text()))
    assert digests == LOWERED

    jaxprs = {"forward": [], "gradient": []}
    for shape in ((4, 2048, 16, 128), (1, 128, 16, 128), (1, 256, 16, 128)):
        arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

        def forward(q, k, v):
            return attention.flash_attention(q, k, v, causal=True)

        jaxprs["forward"].append(str(jax.make_jaxpr(forward)(arg, arg, arg)))
        jaxprs["gradient"].append(str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(forward(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2)))(arg, arg, arg)))
    assert {name: digest("\n".join(texts))
            for name, texts in jaxprs.items()} == KERNEL_JAXPRS


# what a program gets of the chip's 16 GiB (``bytes_limit`` of a v5e's
# ``memory_stats()``), and what the compiler's count of the train step
# must leave of it for what it cannot see (the runtime's own buffers,
# the batch made on the device beside the step, fragmentation): 1.5
# GiB, a tenth of the chip
PROGRAM_BYTES = 16_909_336_064
TRAIN_STEP_SPARE = 3 * 2 ** 29


def test_the_train_step_keeps_what_its_backward_reads_and_fits(
        one_chip, no_compile_cache, monkeypatch):
    """The train cell's step, compiled whole for the described chip:
    one ``flash_fwd`` a layer's body and the backward's two (the rule
    of ``remat=True`` keeps what the backward kernels read, so the
    backward runs no second forward); ``params`` and ``opt_state``
    aliased to the results, every byte of them (the step consumes its
    state); and arguments + results - aliased + temporaries, which with
    the layers' products kept is most of the chip, under the 15.75 GiB
    a program gets by ``TRAIN_STEP_SPARE``."""
    attention = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)

    lowered, state_bytes = train_step_lowered(one_chip)
    compiled = lowered.compile()
    assert mosaic_calls(compiled.as_text()) == sorted(KERNELS)
    memory = compiled.memory_analysis()
    assert 4.2e9 < state_bytes < 4.4e9
    # (the device pads a leaf to whole tiles: some 0.1 MB over the tree)
    assert 0 <= memory.alias_size_in_bytes - state_bytes < 2 ** 20
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert held < PROGRAM_BYTES - TRAIN_STEP_SPARE, held


# --------------- Mamba layers beside attention layers, two kinds of state

HYBRID_CELL = "jamba2-3b.rollout-closed"
LOWERED_HYBRID = {
    "decode": "13ca2a6ead239fab",
    "prefill-128": "5dfd27e60294ead6",
    "prefill-256": "d9740ea2bc50b407",
    "prefill-512": "b3b436c3575270ac",
}


@pytest.fixture(scope="module")
def hybrid_cell():
    """The cell of the model with Mamba and attention layers, as the
    benchmark's worker builds it."""
    from benchmarks import loader

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, HYBRID_CELL)
    config = loader.load_config(bench, cell["config"])
    mix = loader.load_traffic(bench, cell["traffic"])
    program = loader.family_module(loader.find_family(bench, config),
                                   "program")
    return (program.program_config(config, mix["slot_len"]),
            int(mix["slots"]), int(mix["slot_len"]),
            sorted(mix["prompt_lengths"]))


@pytest.fixture
def as_on_the_tpu(monkeypatch):
    """This process's default backend is the CPU; the programs are for
    the described chip, so take the branches a TPU process takes."""
    for module in ("ops.attention", "ops.ssm", "ops.retention",
                   "parallel.experts"):
        monkeypatch.setattr(importlib.import_module("ray_tpu." + module),
                            "_on_tpu", lambda: True)


@pytest.mark.parametrize("length", [128, 256, 512])
def test_the_scan_kernel_compiles_for_v5e_under_its_own_name(
        length, one_chip, no_compile_cache, as_on_the_tpu):
    """``ssm_scan`` at the third serving cell's widths (5120 channels, a
    state of 16, bfloat16 in, float32 dt and state) and its three
    prompt lengths: Mosaic accepts it at the blocks ``ops.ssm``
    chooses, and the program names its one custom call ``ssm_scan``,
    which is what the benchmark's reader looks up in a device trace."""
    import jax
    import jax.numpy as jnp

    ssm = importlib.import_module("ray_tpu.ops.ssm")

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    C, N = 5120, 16
    text = jax.jit(ssm.selective_scan).lower(
        array((1, length, C), jnp.bfloat16),
        array((1, length, C), jnp.float32), array((N, C), jnp.float32),
        array((1, length, N), jnp.bfloat16),
        array((1, length, N), jnp.bfloat16), array((C,), jnp.float32),
        array((1, N, C), jnp.float32)).compile().as_text()
    assert "%ssm_scan.1 = " in text
    assert mosaic_calls(text) == [SCAN_KERNEL]


def test_the_step_kernel_compiles_for_v5e_under_its_own_name(
        one_chip, no_compile_cache, as_on_the_tpu):
    """``ssm_step`` at the third serving cell's decode shape (256 rows,
    a state of 16 a channel, 5120 channels, bfloat16 in, float32 dt and
    state) over its longest run's state (13 layers, 1.09 GB): Mosaic
    accepts it at the blocks ``ops.ssm`` chooses, the program names its
    one custom call ``ssm_step``, and the state array aliases the
    result: what the program holds beside it is the rows' B and C laid
    down the sublanes, a few MB."""
    import jax
    import jax.numpy as jnp

    ssm = importlib.import_module("ray_tpu.ops.ssm")

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, layers, C, N = 256, 13, 5120, 16
    assert ssm.step_blocks(rows, N, C) is not None
    compiled = jax.jit(ssm.carried_step, donate_argnums=(6,)).lower(
        array((rows, C), jnp.bfloat16), array((rows, C), jnp.float32),
        array((N, C), jnp.float32), array((rows, N), jnp.bfloat16),
        array((rows, N), jnp.bfloat16), array((C,), jnp.float32),
        array((layers, rows, N, C), jnp.float32), array((), jnp.int32),
        array((rows,), jnp.bool_)).compile()
    memory = compiled.memory_analysis()
    state = 4 * layers * rows * N * C
    assert 0 <= memory.alias_size_in_bytes - state <= 8192
    assert memory.temp_size_in_bytes < 8 * 2 ** 20
    text = compiled.as_text()
    assert "%ssm_step.1 = " in text
    assert mosaic_calls(text) == [STEP_KERNEL]


def test_the_third_serving_cells_shapes_are_the_ones_compiled_here(
        hybrid_cell):
    from ray_tpu.models.transformer import layer_runs

    cfg, slots, slot_len, lengths = hybrid_cell
    assert (slots, slot_len, lengths) == (256, 2048, [128, 256, 512])
    assert (cfg.n_heads, cfg.head_dim, cfg.kv_heads("full"), cfg.rope,
            cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv,
            cfg.d_ff, cfg.vocab, cfg.tie_embeddings) == (
        20, 128, 1, False, 5120, 16, 160, 4, 8192, 65536, True)
    assert [(kind[0], n) for kind, n in layer_runs(cfg)] == [
        ("mamba", 7), ("full", 1), ("mamba", 13), ("full", 1), ("mamba", 6)]


@pytest.mark.parametrize("program", ["decode", "prefill-128",
                                     "prefill-256", "prefill-512"])
def test_recurrent_state_beside_kv_is_still_written_in_place(
        program, hybrid_cell, one_chip, no_compile_cache, as_on_the_tpu):
    """``slot_decode_step`` and ``slot_prefill`` of the model with Mamba
    layers, at its cell's shapes, for the described v5e. Every leaf of
    the cache is aliased to the result (2.92 GB: 2.18 GB of recurrent
    state in float32, 0.20 GB of convolution tails, 0.54 GB of K and V
    for the two attention layers); no operation produces an array of a
    run's or a layer's state, tail, K or V but the in-place writes and,
    in a decode step, the step's own kernel, whose operand and result a
    run's whole state is; the temporaries stay under one layer's state
    (84 MB: no copy of one has room); arguments and temporaries fit the
    chip; each prefill holds both kernels, one call a run of layers,
    and a decode step one ``decode_attend`` an attention run and one
    ``ssm_step`` a Mamba run."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode, init_params

    cfg, slots, slot_len, _ = hybrid_cell

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = described(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, slot_len)))
    shape_of = lambda name: [None if a is None else a.shape  # noqa: E731
                             for a in cache[name]]
    assert shape_of("ssm") == [(7, 256, 16, 5120), None,
                               (13, 256, 16, 5120), None,
                               (6, 256, 16, 5120)]
    assert shape_of("conv") == [(7, 3, 256, 5120), None, (13, 3, 256, 5120),
                                None, (6, 3, 256, 5120)]
    assert shape_of("k") == shape_of("v") == [
        None, (1, 256, 2048, 128), None, (1, 256, 2048, 128), None]
    if program == "decode":
        compiled = compiled_decode(HYBRID_CELL, cfg, slots, slot_len,
                                   one_chip)
    else:
        length = int(program.split("-")[1])
        compiled = decode.slot_prefill.lower(
            params, array((1, length), jnp.int32), cache,
            array((), jnp.int32), cfg).compile()
    text = compiled.as_text()

    # every leaf of the cache, and nothing else, aliases the result:
    # state and tail of the three Mamba runs, K and V of the two
    # attention runs, pos and tok
    leaves = re.findall(
        r"parameter\((\d+)\)[^\n]*op_name=\"cache\[([^\"]*)\]\"", text)
    assert len(leaves) == 3 + 3 + 2 + 2 + 2
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliased, "nothing is aliased: the cache is not donated"
    assert sorted(int(n) for n in re.findall(
        r"\((\d+), \{\}, may-alias\)", aliased.group(1))
    ) == sorted(int(n) for n, _ in leaves)

    held = [leaf for name in ("ssm", "conv", "k", "v")
            for leaf in cache[name] if leaf is not None]
    state_bytes = sum(leaf.dtype.itemsize * math.prod(leaf.shape)
                      for leaf in held)
    memory = compiled.memory_analysis()
    assert 0 <= memory.alias_size_in_bytes - state_bytes <= 8192
    assert memory.temp_size_in_bytes < 4 * 256 * 16 * 5120
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < HBM_BYTES)

    shapes = {leaf.shape for leaf in held} | {leaf.shape[1:]
                                              for leaf in held}
    produced = [(name, op) for name, op in cache_producers(text, shapes)
                if op != "bitcast"]
    allowed = set(IN_PLACE) | (
        {"tpu_custom_call"} if program == "decode" else set())
    assert {op for _, op in produced} <= allowed, produced
    # a write a run and kind of state; a decode step's Mamba states are
    # the kernel's own results, one tuple with y, which no shape above
    # names
    assert len(produced) == (3 + 2 * 2 if program == "decode"
                             else 3 * 2 + 2 * 2), produced
    assert mosaic_calls(text) == (
        [DECODE_KERNEL] * 2 + [STEP_KERNEL] * 3 if program == "decode"
        else ["flash_fwd"] * 2 + [SCAN_KERNEL] * 3)


def test_the_third_cells_decode_step_hands_the_host_a_row_of_picks(
        hybrid_cell, one_chip, no_compile_cache, as_on_the_tpu):
    cfg, slots, slot_len, _ = hybrid_cell
    compiled = compiled_decode(HYBRID_CELL, cfg, slots, slot_len, one_chip)
    text = compiled.as_text()
    root = re.search(r"ROOT %[\w.\-]+ = \(([^\n]*?)\) tuple\(",
                     text[text.index("\nENTRY "):]).group(1)
    results = re.findall(r"(\w+)\[([\d,]*)\]", root)
    # the row of picks; beside it the cache's pos and tok
    assert results.count(("s32", str(slots))) == 3
    assert not [r for r in results
                if r[1] == f"{slots},{cfg.vocab}"], results
    memory = compiled.memory_analysis()
    assert 4 * slots <= (memory.output_size_in_bytes
                         - memory.alias_size_in_bytes) <= 4096


def test_the_third_cells_serving_programs_lower_to_the_text_on_record(
        hybrid_cell, one_chip, as_on_the_tpu):
    assert serving_programs_lowered(hybrid_cell, one_chip) == LOWERED_HYBRID


# --------------- retention layers alone: a matrix state and no K/V at all

RETENTION_CELL = "brumby-14b-d8.longdoc-closed"
LOWERED_RETENTION = {
    "decode": "04d22836d1841314",
    "prefill-2048": "506ce466008ff5d8",
    "prefill-4096": "de88dcd2d68124f8",
    "prefill-8192": "8ac4d211427ad764",
}


@pytest.fixture(scope="module")
def retention_cell():
    """The cell of the model whose every mixer is a retention layer, as
    the benchmark's worker builds it."""
    from benchmarks import loader

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, RETENTION_CELL)
    config = loader.load_config(bench, cell["config"])
    mix = loader.load_traffic(bench, cell["traffic"])
    program = loader.family_module(loader.find_family(bench, config),
                                   "program")
    return (program.program_config(config, mix["slot_len"]),
            int(mix["slots"]), int(mix["slot_len"]),
            sorted(mix["prompt_lengths"]))


def test_the_fourth_serving_cells_shapes_are_the_ones_compiled_here(
        retention_cell):
    from ray_tpu.models.transformer import layer_runs

    cfg, slots, slot_len, lengths = retention_cell
    assert (slots, slot_len, lengths) == (16, 9216, [2048, 4096, 8192])
    assert (cfg.n_heads, cfg.head_dim, cfg.kv_heads("retention"),
            cfg.qk_norm, cfg.rope, cfg.d_model,
            cfg.d_ff, cfg.vocab, cfg.tie_embeddings, cfg.max_seq) == (
        40, 128, 8, True, True, 5120, 17408, 151936, False, 9216)
    assert [(kind[0], n) for kind, n in layer_runs(cfg)] == [
        ("retention", 8)]
    # a prefill on either side of the rule that keeps the flat q, k and
    # v products from their reshape to heads (rows fewer than d_model)
    assert lengths[0] < lengths[1] < cfg.d_model < lengths[2]


@pytest.mark.parametrize("kernel", ["chunk-2048", "chunk-8192", "step"])
def test_the_retention_kernels_compile_for_v5e_under_their_own_names(
        kernel, one_chip, no_compile_cache, as_on_the_tpu):
    """``retention_chunk`` and ``retention_step`` at the fourth serving
    cell's widths (40 query heads on 8 K/V heads of 128, bfloat16 in, a
    float32 state [128, 8320] a head): Mosaic accepts them at the
    blocks ``ops.retention`` chooses, and the program names its one
    custom call after the kernel, which is what the benchmark's readers
    look up in a device trace. The step's state arrays, the run's whole
    (4.4 GB at 16 slots), alias its results: the kernel's own
    temporaries are some hundreds of KB."""
    import jax
    import jax.numpy as jnp

    ret = importlib.import_module("ray_tpu.ops.retention")

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    D = ret.feature_dim(128)
    assert D == 8320
    if kernel == "step":
        slots, layers = 16, 8
        compiled = jax.jit(ret.retention_step, donate_argnums=(4, 5)).lower(
            array((slots, 40, 128), jnp.bfloat16),
            array((slots, 8, 128), jnp.bfloat16),
            array((slots, 8, 128), jnp.bfloat16),
            array((slots, 8), jnp.float32),
            array((layers, slots, 8, 128, D), jnp.float32),
            array((layers, slots, 8, D), jnp.float32),
            array((), jnp.int32), array((slots,), jnp.bool_)).compile()
        memory = compiled.memory_analysis()
        state = 4 * layers * slots * 8 * (128 + 1) * D
        assert 0 <= memory.alias_size_in_bytes - state <= 8192
        assert memory.temp_size_in_bytes < 2 ** 20
        name = "retention_step"
    else:
        length = int(kernel.split("-")[1])
        compiled = jax.jit(ret.retention).lower(
            array((1, length, 40, 128), jnp.bfloat16),
            array((1, length, 8, 128), jnp.bfloat16),
            array((1, length, 8, 128), jnp.bfloat16),
            array((1, length, 8), jnp.float32)).compile()
        name = "retention_chunk"
    text = compiled.as_text()
    assert f"%{name}.1 = " in text
    assert mosaic_calls(text) == [name]


@pytest.mark.parametrize("program", ["decode", "prefill-2048",
                                     "prefill-8192"])
def test_a_matrix_state_and_no_kv_is_written_in_place(
        program, retention_cell, one_chip, no_compile_cache, as_on_the_tpu):
    """``slot_decode_step`` and ``slot_prefill`` of the model of
    retention layers alone, at its cell's shapes, for the described
    v5e. The cache has no key and no value leaf; its four leaves (the
    run's state, 4.36 GB, its normaliser, pos and tok) are aliased to
    the result; no operation produces an array of the run's or of a
    layer's state or normaliser but the in-place writes and, in a
    decode step, the step's own kernel, whose results they are; a
    decode step's temporaries stay in the tens of MB (a copy of one
    layer's state of the 16 slots is 545 MB, of the run's 4.4 GB);
    arguments and temporaries fit the chip; a decode step holds one
    ``retention_step`` call for the run, a prefill one
    ``retention_chunk``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode, init_params

    cfg, slots, slot_len, _ = retention_cell

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = described(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, slot_len)))
    assert set(cache) == {"pos", "tok", "ret", "ret_z"}
    assert [a.shape for a in cache["ret"]] == [(8, 16, 8, 128, 8320)]
    assert [a.shape for a in cache["ret_z"]] == [(8, 16, 8, 8320)]
    if program == "decode":
        compiled = compiled_decode(RETENTION_CELL, cfg, slots, slot_len,
                                   one_chip)
    else:
        length = int(program.split("-")[1])
        compiled = decode.slot_prefill.lower(
            params, array((1, length), jnp.int32), cache,
            array((), jnp.int32), cfg).compile()
    text = compiled.as_text()

    leaves = re.findall(
        r"parameter\((\d+)\)[^\n]*op_name=\"cache\[([^\"]*)\]\"", text)
    assert len(leaves) == 4
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliased, "nothing is aliased: the cache is not donated"
    assert sorted(int(n) for n in re.findall(
        r"\((\d+), \{\}, may-alias\)", aliased.group(1))
    ) == sorted(int(n) for n, _ in leaves)

    held = list(cache["ret"] + cache["ret_z"])
    state_bytes = sum(4 * math.prod(leaf.shape) for leaf in held)
    memory = compiled.memory_analysis()
    assert 0 <= memory.alias_size_in_bytes - state_bytes <= 8192
    one_layer = state_bytes // 8
    assert memory.temp_size_in_bytes < (
        one_layer // 8 if program == "decode" else 4 * one_layer)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < HBM_BYTES)

    shapes = {leaf.shape for leaf in held} | {leaf.shape[1:]
                                              for leaf in held}
    # a bitcast names memory; a copy-start/copy-done pair (with the
    # slices a ``ConcatBitcast`` names as one array) is the compiler's
    # own move of the run's normaliser (34 MB, a hundredth of the
    # state) into the chip's fast memory for the length of the step
    produced = [(name, op) for name, op in cache_producers(text, shapes)
                if op not in ("bitcast", "copy-start", "copy-done",
                              "ConcatBitcast")]
    allowed = set(IN_PLACE) | (
        {"tpu_custom_call"} if program == "decode" else set())
    assert {op for _, op in produced} <= allowed, produced
    # a prefill's write of the state and of its normaliser; a decode
    # step's are the kernel's own results, one tuple, which no shape
    # above names
    assert len(produced) == (0 if program == "decode" else 2), produced
    assert mosaic_calls(text) == [
        "retention_step" if program == "decode" else "retention_chunk"]


def test_the_fourth_cells_decode_step_hands_the_host_a_row_of_picks(
        retention_cell, one_chip, no_compile_cache, as_on_the_tpu):
    cfg, slots, slot_len, _ = retention_cell
    compiled = compiled_decode(RETENTION_CELL, cfg, slots, slot_len,
                               one_chip)
    text = compiled.as_text()
    root = re.search(r"ROOT %[\w.\-]+ = \(([^\n]*?)\) tuple\(",
                     text[text.index("\nENTRY "):]).group(1)
    results = re.findall(r"(\w+)\[([\d,]*)\]", root)
    # the row of picks; beside it the cache's pos and tok
    assert results.count(("s32", str(slots))) == 3
    assert not [r for r in results
                if r[1] == f"{slots},{cfg.vocab}"], results
    memory = compiled.memory_analysis()
    assert 4 * slots <= (memory.output_size_in_bytes
                         - memory.alias_size_in_bytes) <= 4096


def test_the_fourth_cells_serving_programs_lower_to_the_text_on_record(
        retention_cell, one_chip, as_on_the_tpu):
    assert serving_programs_lowered(retention_cell, one_chip) == \
        LOWERED_RETENTION



# ------- one cache that eight layers read, runs of periods of two layers

SHARED_CELL = "phi-4-mini-flash.session-closed"
LOWERED_SHARED = {
    "decode": "52c320289f4c2b61",
    "prefill-1024": "5a92f917fea65eda",
    "prefill-2048": "0de56d1419d9fc90",
    "prefill-4096": "8d660b5aeb509d8d",
}


@pytest.fixture(scope="module")
def shared_cell():
    """The cell of the model whose cross layers read one layer's cache,
    as the benchmark's worker builds it."""
    from benchmarks import loader

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, SHARED_CELL)
    config = loader.load_config(bench, cell["config"])
    mix = loader.load_traffic(bench, cell["traffic"])
    program = loader.family_module(loader.find_family(bench, config),
                                   "program")
    return (program.program_config(config, mix["slot_len"]),
            int(mix["slots"]), int(mix["slot_len"]),
            sorted(mix["prompt_lengths"]))


@pytest.mark.parametrize("program", ["decode", "prefill-1024",
                                     "prefill-2048", "prefill-4096"])
def test_a_cache_that_eight_layers_read_is_still_written_in_place(
        program, shared_cell, one_chip, no_compile_cache, as_on_the_tpu):
    """``slot_decode_step`` and ``slot_prefill`` of the model with one
    growing cache, at its cell's shapes, for the described v5e. Every
    leaf of the cache is aliased to the result (a slot is 55.7 MB: the
    full layer's 6,144 rows, eight rings of 512, nine states and
    tails); no operation produces an array of a run's or a layer's K,
    V, state or tail but the in-place writes and, in a decode step, the
    ``ssm_step`` kernel: **the seven cross layers read the full
    layer's K and V where its run left them**, no copy and no slice of
    it; the temporaries stay far under that layer's K (a decode step's
    in the MB, a prefill's under 0.4 GB of activations); arguments and
    temporaries fit the chip; a decode step holds two ``decode_attend``
    calls (the full layer's own and the one in the cross run's loop),
    one ``decode_ring`` (the window layers', in their run's loop) and
    one ``ssm_step`` a run with Mamba layers, a prefill one
    ``flash_fwd`` (the window run's; the full layer attends from the
    prompt's last position alone) and one ``ssm_scan`` a Mamba run."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode, init_params
    from ray_tpu.models.transformer import layer_runs

    cfg, slots, slot_len, _ = shared_cell
    assert [(tuple(m for m, _ in kind), n) for kind, n in layer_runs(cfg)
            ] == [(("mamba", "window"), 8), (("mamba", "full"), 1),
                  (("gmu", "cross"), 7)]

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = described(jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg)))
    cache = described(jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, slot_len)))
    shape_of = lambda name: [[None if a is None else a.shape  # noqa: E731
                              for a in run] for run in cache[name]]
    assert shape_of("ssm") == [[(8, slots, 16, 5120), None],
                               [(1, slots, 16, 5120), None], [None, None]]
    assert shape_of("conv") == [[(8, 3, slots, 5120), None],
                                [(1, 3, slots, 5120), None], [None, None]]
    assert shape_of("k") == shape_of("v") == [
        [None, (8, slots, 512, 1280)], [None, (1, slots, slot_len, 1280)],
        [None, None]]
    if program == "decode":
        compiled = compiled_decode(SHARED_CELL, cfg, slots, slot_len,
                                   one_chip)
    else:
        length = int(program.split("-")[1])
        compiled = decode.slot_prefill.lower(
            params, array((1, length), jnp.int32), cache,
            array((), jnp.int32), cfg).compile()
    text = compiled.as_text()

    # every leaf of the cache, and nothing else, aliases the result:
    # state and tail of the two runs with Mamba layers, K and V of the
    # window run and of the full layer, pos and tok
    leaves = re.findall(
        r"parameter\((\d+)\)[^\n]*op_name=\"cache\[([^\"]*)\]\"", text)
    assert len(leaves) == 2 * 2 + 2 * 2 + 2
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_comp", text)
    assert aliased, "nothing is aliased: the cache is not donated"
    assert sorted(int(n) for n in re.findall(
        r"\((\d+), \{\}, may-alias\)", aliased.group(1))
    ) == sorted(int(n) for n, _ in leaves)

    held = [leaf for name in ("ssm", "conv", "k", "v")
            for leaf in jax.tree.leaves(cache[name])]
    state_bytes = sum(leaf.dtype.itemsize * math.prod(leaf.shape)
                      for leaf in held)
    assert round(state_bytes / slots / 1e6, 1) == 55.7
    memory = compiled.memory_analysis()
    assert 0 <= memory.alias_size_in_bytes - state_bytes <= 8192
    # the full layer's K of all slots is 1.0 GB: no copy of it has room
    assert memory.temp_size_in_bytes < (
        16 * 2 ** 20 if program == "decode" else 400 * 2 ** 20)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < HBM_BYTES)

    shapes = {leaf.shape for leaf in held} | {leaf.shape[1:]
                                              for leaf in held}
    # (the compiler's own prefetch into fast memory of the 2 MB that are
    # the single Mamba layer's tails, ``copy-start`` / ``copy-done``, is
    # no copy in memory)
    produced = [(name, op) for name, op in cache_producers(text, shapes)
                if op not in ("bitcast", "copy-start", "copy-done")]
    allowed = set(IN_PLACE) | (
        {"tpu_custom_call"} if program == "decode" else set())
    assert {op for _, op in produced} <= allowed, produced
    # a write a run and kind of state that the run keeps: K and V of
    # the window run and of the full layer, the tails of the two Mamba
    # runs (their states are the step kernel's own results in a decode
    # step, one tuple with y, which no shape above names; a prefill
    # writes them; of the single Mamba layer's tails, 2 MB, a decode
    # step replaces the whole array, which is no write into one). The
    # cross run writes nothing
    assert len(produced) == (2 * 2 + 1 if program == "decode"
                             else 2 * 2 + 2 * 2), produced
    assert mosaic_calls(text) == (
        sorted([DECODE_KERNEL] * 2 + [DECODE_RING] + [STEP_KERNEL] * 2)
        if program == "decode" else ["flash_fwd"] + [SCAN_KERNEL] * 2)


def test_the_fifth_cells_decode_step_hands_the_host_a_row_of_picks(
        shared_cell, one_chip, no_compile_cache, as_on_the_tpu):
    cfg, slots, slot_len, _ = shared_cell
    compiled = compiled_decode(SHARED_CELL, cfg, slots, slot_len, one_chip)
    text = compiled.as_text()
    root = re.search(r"ROOT %[\w.\-]+ = \(([^\n]*?)\) tuple\(",
                     text[text.index("\nENTRY "):]).group(1)
    results = re.findall(r"(\w+)\[([\d,]*)\]", root)
    # the row of picks; beside it the cache's pos and tok
    assert results.count(("s32", str(slots))) == 3
    assert not [r for r in results
                if r[1] == f"{slots},{cfg.vocab}"], results
    # every part the layers' kinds imply names an instruction, the two
    # new ones among them, and a cross layer's kernel call is its part's
    from ray_tpu.models import decode

    table = decode.program_parts(text, decode.decode_parts(cfg))
    assert table is not None
    parts = {(run, part) for run, part in table.values()}
    assert {("run2", "gmu"), ("run2", "cross_attention"),
            ("run1", "full_attention"), ("run0", "window_attention"),
            ("run0", "ssm_step"), ("run1", "ssm_step")} <= parts
    attends = sorted(tuple(where) for name, where in table.items()
                     if name.startswith(DECODE_KERNEL))
    assert attends == [("run1", "full_attention"),
                       ("run2", "cross_attention")]
    rings = [tuple(where) for name, where in table.items()
             if name.startswith(DECODE_RING)]
    assert rings == [("run0", "window_attention")]


def test_the_fifth_cells_serving_programs_lower_to_the_text_on_record(
        shared_cell, one_chip, as_on_the_tpu):
    assert serving_programs_lowered(shared_cell, one_chip) == LOWERED_SHARED


# --------------- q, k and v read out of the stacked weights where they lie

CELL_FIXTURES = {SERVING_CELL: "serving_cell", KINDS_CELL: "kinds_cell",
                 HYBRID_CELL: "hybrid_cell",
                 RETENTION_CELL: "retention_cell",
                 SHARED_CELL: "shared_cell"}


@pytest.mark.parametrize("cell", sorted(CELL_FIXTURES))
def test_a_decode_steps_q_k_v_products_read_the_stack_where_it_lies(
        cell, request, one_chip, no_compile_cache, as_on_the_tpu):
    """The five serving cells' decode steps as compiled for the
    described v5e (``compiled_decode``: no compile where a test above
    has run). Outside fused computations no ``copy`` and no fusion
    rooted in a ``dynamic-slice`` yields an array of a layer's ``wq``,
    ``wk`` or ``wv`` (as many elements, ``d_model`` among its
    dimensions, whatever its layout or memory space): no layer's matrix
    is sliced into fast memory as an operation of its own, none is
    relaid, in a run that kept its loop and in a run XLA unrolled. The
    compiler's own prefetches (``copy-start`` / ``copy-done``) are not
    counted, nor a copy of the rows' flat result ([slots, 1, H * Dh]).
    And in a run that kept its loop each of the three products under
    the scope ``qkv`` (four with a retention layer's gate) takes the
    stacked leaf itself among its operands,
    as ``wo``'s and the feed-forward's do. With the reshape to heads
    folded into the product XLA asks for the weight transposed, and
    this fails in all three cells: six such instructions in cell 1,
    ten in cell 4, two in cell 5."""
    import jax

    from ray_tpu.models import init_params
    from ray_tpu.models.transformer import layer_stacks

    cfg, slots, slot_len, _ = request.getfixturevalue(CELL_FIXTURES[cell])
    text = compiled_decode(cell, cfg, slots, slot_len, one_chip).as_text()
    # (a retention layer's gate is a fourth product of the part)
    # (... and a cross layer's only product is q's; a run of periods of
    # several layers holds a stack for each layer of the period)
    stacks = [{name: stack[name].shape
               for name in ("wq", "wk", "wv", "w_g") if name in stack}
              for _, run in layer_stacks(jax.eval_shape(
                  lambda: init_params(jax.random.key(0), cfg)), cfg)
              for stack in (run if isinstance(run, tuple) else (run,))
              if "wq" in stack]
    sizes = {math.prod(shape[1:]) for run in stacks for shape in run.values()}
    found = list(outside_fusions(text))

    # a layer's matrix sliced out or relaid
    made = [(name, shape, does) for _, name, shape, does, _ in found
            if does in ("copy", "dynamic-slice") and cfg.d_model in shape
            and math.prod(shape) in sizes]
    assert not made, made

    # the products of the runs that kept their loop: in a loop's body,
    # not in the entry computation (an unrolled run's carry their
    # ``while/body`` path there too)
    entry = re.search(r"^ENTRY %([\w.\-]+) ", text, re.M).group(1)
    shape_of = {(comp, name): shape for comp, name, shape, _, _ in found}
    products = [
        (name, [shape_of.get((comp, operand)) for operand in re.findall(
            r"%([\w.\-]+)", rest.split(")")[0])])
        for comp, name, _, does, rest in found
        if comp != entry and does not in ("parameter", "get-tuple-element")
        and re.search(r'op_name="[^"]*/qkv/dot_general"', rest)]
    looped = [run for run in stacks if run["wq"][0] > 1]
    assert len(products) == sum(len(run) for run in looped), products
    leaves = {shape for run in looped for shape in run.values()}
    assert all(leaves & set(operands) for _, operands in products), products
