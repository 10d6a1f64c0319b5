"""Device ms a decode step in what a run's loop holds outside every part: the scan's slices of the stacked weights and the copies hung on them."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('layer_weights',))
