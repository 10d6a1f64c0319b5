"""The ``toy`` family's program: a slot engine of its own that keeps the
protocol (``prefill(slot, prompt) -> int``, ``step({slot: token}) ->
{slot: token}``, ``slots``, ``max_len``) and holds no state, since a
token's successor depends on that token alone. It serves only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def program_config(config: dict, max_seq: int) -> float:
    return float(config["norm_eps"])


@jax.jit
def _greedy(params, tokens, eps):
    x = params["embed"][tokens]
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return jnp.argmax((x * params["norm"]) @ params["embed"].T, axis=-1)


class ToyEngine:
    def __init__(self, params, eps, slots, max_len):
        self.params, self.eps = params, eps
        self.slots, self.max_len = slots, max_len

    def _next(self, tokens):
        """One shape for every call, so that nothing compiles later."""
        padded = list(tokens) + [0] * (self.slots - len(tokens))
        out = _greedy(self.params, jnp.asarray(padded, jnp.int32), self.eps)
        return [int(t) for t in out[:len(tokens)]]

    def prefill(self, slot, prompt):
        return self._next(prompt[-1:])[0]

    def step(self, tokens):
        slots = list(tokens)
        return dict(zip(slots, self._next([tokens[s] for s in slots])))


def make_engine(params, cfg, slots: int, max_len: int):
    return ToyEngine(params, cfg, slots, max_len)


def prefill_programs(params, cfg, slots, max_len, lengths) -> dict:
    """No kernels: each length's program is the one lookup."""
    text = _greedy.lower(params, jnp.zeros((slots,), jnp.int32),
                         cfg).compile().as_text()
    return {length: text for length in lengths}
