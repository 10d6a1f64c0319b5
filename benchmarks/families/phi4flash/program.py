"""The ``phi4flash`` family as the program runs it. The one file of the
family that imports ``ray_tpu``'s model code: the program's own config
object for a configuration file, its slot engine and the prefill
programs it compiles. The benchmark wraps their calls
(``benchmarks/worker.py``) and changes nothing inside. The weights are
``reference.seeded_params``'s, whose layout (a tuple of runs, each a
pair of stacks, one for each layer of the run's (Mamba-class,
attention-class) pairs) is the program's own for this pattern
(``transformer.layer_runs``).
"""

from __future__ import annotations

from .reference import sizes_of


def program_config(config: dict, max_seq: int):
    """The program's own ``TransformerConfig`` for a configuration file
    (published key names). What the block cannot express ``sizes_of``
    refuses by its published key."""
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    from ray_tpu.models.transformer import DENSE

    sz = sizes_of(config)       # refuses what the block cannot express
    return TransformerConfig(
        vocab=sz.vocab, d_model=sz.d_model, n_heads=sz.n_heads,
        n_layers=sz.n_layers, d_ff=sz.d_ff, max_seq=int(max_seq),
        dtype=jnp.dtype(sz.dtype).type, norm_eps=sz.eps,
        tie_embeddings=True, n_kv_heads=sz.kv_heads, rope=False,
        # the reference's names of the mixers are the program's
        layer_kinds=tuple((mixer, DENSE) for mixer in sz.mixers),
        window=sz.window, ssm_inner=sz.ssm_inner, ssm_state=sz.ssm_state,
        ssm_dt_rank=sz.ssm_dt_rank, ssm_conv=sz.ssm_conv,
        ssm_inner_norms=False, differential=True, attn_bias=True,
        layer_norm=True)


def make_engine(params, cfg, slots: int, max_len: int):
    """The program's slot engine: ``prefill(slot, prompt) -> int``,
    ``step({slot: token}) -> {slot: token}``, ``slots``, ``max_len``."""
    from ray_tpu import serve

    return serve.JaxSlotEngine(params, cfg, slots=slots, max_len=max_len)


def prefill_programs(params, cfg, slots: int, max_len: int,
                     lengths) -> dict:
    """{prompt length: text of the compiled prefill program}: the same
    jit the engine calls, so a cache hit after the warm-up. The harness
    counts the Mosaic calls in each (the window layers' flash kernel
    and the Mamba layers' scan)."""
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
        "chip: the smallest that holds a period of each decoder and the "
        "layers between them is eight layers, some 0.83 B parameters, "
        "and an eighth of the vocabulary 64 M more: 0.9 B, 14.3 GB at "
        "16 bytes a parameter with gradients and Adam's state, before "
        "any activation")
