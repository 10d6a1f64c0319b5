"""Device ms a decode step in the retention's state pass: each live row's state read, advanced and written where it lies."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('retention_step',))
