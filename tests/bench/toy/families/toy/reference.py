"""The plain reference of the ``toy`` family, which only
``tests/bench/test_family_seam.py`` runs: an embedding, one RMSNorm and
the tied unembedding. A token's logits depend on that token alone.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import mm, seed_key


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    eps: float


def sizes_of(config: dict) -> Sizes:
    if not config.get("tie_word_embeddings", False):
        raise ValueError("the toy block cannot express: untied embeddings")
    return Sizes(vocab=int(config["vocab_size"]),
                 d_model=int(config["hidden_size"]),
                 eps=float(config["norm_eps"]))


@functools.partial(jax.jit, static_argnames=("sz",))
def _params(key, sz: Sizes):
    return {"embed": jax.random.normal(key, (sz.vocab, sz.d_model)),
            "norm": jnp.ones((sz.d_model,))}


def seeded_params(seed: int, sz: Sizes):
    return _params(seed_key(seed), sz)


def forward(params, tokens, sz: Sizes, quant=None, remat=False):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    x = params["embed"][tokens]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + sz.eps)
    return mm(x * params["norm"], params["embed"].T, quant)


def by_leaf(tree):
    return dict(tree)
