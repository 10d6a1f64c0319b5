"""Device ms a decode step in a run's loop body outside every part: the scan's slice of each stacked weight and the copies XLA hangs on it."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('layer_weights',))
