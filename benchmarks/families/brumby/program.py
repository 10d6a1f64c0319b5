"""The ``brumby`` family as the program runs it. The one file of the
family that imports ``ray_tpu``'s model code: the program's own config
object for a configuration file, its slot engine and the prefill
programs it compiles. The benchmark wraps their calls
(``benchmarks/worker.py``) and changes nothing inside. The weights are
``reference.seeded_params``'s, whose layout (a tuple of one stack of
retention layers, an untied head) is the program's own.
"""

from __future__ import annotations

from .reference import sizes_of


def program_config(config: dict, max_seq: int):
    """The program's own ``TransformerConfig`` for a configuration file
    (published key names). What the block cannot express (a sliding
    window in use, rope scaling, biased projections, tied embeddings)
    ``sizes_of`` refuses by its published key. ``max_seq`` sizes the
    rope's table and nothing else: no layer keeps a row a position."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    from ray_tpu.models.transformer import DENSE, RETENTION

    sz = sizes_of(config)       # refuses what the block cannot express
    return TransformerConfig(
        vocab=sz.vocab, d_model=sz.d_model, n_heads=sz.n_heads,
        n_layers=sz.n_layers, d_ff=sz.d_ff, max_seq=int(max_seq),
        rope_theta=sz.rope_theta, dtype=jnp.dtype(sz.dtype).type,
        norm_eps=sz.eps, tie_embeddings=False, n_kv_heads=sz.kv_heads,
        qk_head_dim=sz.head_dim, qk_norm=True,
        layer_kinds=((RETENTION, DENSE),) * sz.n_layers)


def make_engine(params, cfg, slots: int, max_len: int):
    """The program's slot engine: ``prefill(slot, prompt) -> int``,
    ``step({slot: token}) -> {slot: token}``, ``slots``, ``max_len``."""
    from ray_tpu import serve

    return serve.JaxSlotEngine(params, cfg, slots=slots, max_len=max_len)


def prefill_programs(params, cfg, slots: int, max_len: int,
                     lengths) -> dict:
    """{prompt length: text of the compiled prefill program}: the same
    jit the engine calls, so a cache hit after the warm-up. The harness
    counts the Mosaic calls in each (the retention layers' chunk
    kernel)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import decode

    cache = jax.eval_shape(
        lambda: decode.init_slot_cache(cfg, slots, max_len))
    return {length: decode.slot_prefill.lower(
        params, jax.ShapeDtypeStruct((1, length), jnp.int32), cache,
        jnp.int32(0), cfg).compile().as_text() for length in lengths}


def make_train_step(cfg, mix: dict):
    raise NotImplementedError(
        "no cut of this model within the sizing floors trains on one "
        "chip: four layers and the vocabulary are 4 x 330.35 M + "
        "1,555.8 M = 2,877 M parameters, 46.0 GB at 16 bytes a parameter "
        "with gradients and Adam's state")
