"""The ``ouro`` family as the program runs it. The one file of the
benchmark that imports ``ray_tpu``'s model code: the program's own
config object for a configuration file, its slot engine, the prefill
programs it compiles and its train step. The benchmark wraps their
calls (``benchmarks/worker.py``) and changes nothing inside.
"""

from __future__ import annotations

from .reference import sizes_of


def program_config(config: dict, max_seq: int):
    """The program's own ``TransformerConfig`` for a configuration file
    (published key names)."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig

    sz = sizes_of(config)       # refuses what the block cannot express
    return TransformerConfig(
        vocab=sz.vocab, d_model=sz.d_model, n_heads=sz.n_heads,
        n_layers=sz.n_layers, d_ff=sz.d_ff, max_seq=int(max_seq),
        rope_theta=sz.rope_theta, dtype=jnp.dtype(sz.dtype).type)


def make_engine(params, cfg, slots: int, max_len: int):
    """The program's slot engine: ``prefill(slot, prompt) -> int``,
    ``step({slot: token}) -> {slot: token}``, ``slots``, ``max_len``."""
    from ray_tpu import serve

    return serve.JaxSlotEngine(params, cfg, slots=slots, max_len=max_len)


def prefill_programs(params, cfg, slots: int, max_len: int,
                     lengths) -> dict:
    """{prompt length: text of the compiled prefill program}: the same
    jit the engine calls, so a cache hit after the warm-up. The harness
    counts the Mosaic calls in each."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode

    cache = jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, max_len))
    return {length: decode.slot_prefill.lower(
        params, jax.ShapeDtypeStruct((1, length), jnp.int32), cache,
        jnp.int32(0), cfg).compile().as_text() for length in lengths}


def make_train_step(cfg, mix: dict):
    """(jitted ``step(params, opt_state, batch) -> (params, opt_state,
    loss)``, its optax optimizer) for a training mix."""
    from ray_tpu.models import ParallelConfig, make_train_step

    return make_train_step(cfg, ParallelConfig(remat=bool(mix["remat"])))
