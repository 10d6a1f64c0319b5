"""Device ms a decode step in the gated memory units: norm, both projections, the gate on the memory, residual."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('gmu',))
