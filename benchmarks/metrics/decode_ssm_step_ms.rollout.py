"""Device ms a decode step in the recurrence alone: the state read, advanced and written where it lies."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('ssm_step',))
