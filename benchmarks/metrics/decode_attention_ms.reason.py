"""Device ms a decode step in both attention kinds: the two full layers' kernel and the five window rings, with the cache's in-place writes."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('full_attention', 'window_attention'))
