"""Device ms a decode step in the attention norm, the three projections, rope and the value scale."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('qkv',))
