"""Device ms a decode step in the dense feed-forwards: norm, gate, up, down and residual."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('mlp',))
