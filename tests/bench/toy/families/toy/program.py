"""The ``toy`` family's program: a slot engine of its own that keeps the
protocol (``prefill(slot, prompt) -> int``, ``step({slot: token}) ->
{slot: token}``, ``slots``, ``max_len``) and holds no state, since a
token's successor depends on that token alone. It serves only. A step
times the phases the program's own engine times (``serve.engine.check``,
``put``, ``dispatch``, ``wait``, ``read``) and counts the embedding rows
it looked up beside them, in the scheduler's table, for its
``costs.decode_step_bytes`` to price.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.util.phases import phase, phase_add


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

    def _padded(self, tokens):
        """One shape for every call, so that nothing compiles later."""
        return jnp.asarray(list(tokens) + [0] * (self.slots - len(tokens)),
                           jnp.int32)

    def prefill(self, slot, prompt):
        return int(_greedy(self.params, self._padded(prompt[-1:]),
                           self.eps)[0])

    def step(self, tokens):
        with phase("serve.engine.check"):
            slots = list(tokens)
        with phase("serve.engine.put"):
            padded = self._padded([tokens[s] for s in slots])
        with phase("serve.engine.dispatch"):
            out = _greedy(self.params, padded, self.eps)
        with phase("serve.engine.wait"):
            row = jax.device_get(out)
        with phase("serve.engine.read"):
            phase_add("serve.engine.rows_looked_up", len(slots))
            return dict(zip(slots, row.tolist()))


def make_engine(params, cfg, slots: int, max_len: int):
    return ToyEngine(params, cfg, slots, max_len)


def prefill_programs(params, cfg, slots, max_len, lengths) -> dict:
    """No kernels: each length's program is the one lookup."""
    text = _greedy.lower(params, jnp.zeros((slots,), jnp.int32),
                         cfg).compile().as_text()
    return {length: text for length in lengths}
