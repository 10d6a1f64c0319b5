"""Device ms a decode step in the layers' own attention: the full layer's read of its cache (decode_attend) and the eight window layers' rings, each with the cache's in-place write."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('full_attention', 'window_attention'))
