"""Device ms a decode step in the final norm, the unembed and the pick."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('head',))
