"""Device ms a decode step in scores, softmax and p.V (the decode_attend kernel) with the cache's in-place write."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('full_attention', 'window_attention'))
