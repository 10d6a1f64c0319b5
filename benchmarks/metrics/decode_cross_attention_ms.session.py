"""Device ms a decode step in the cross layers' attention: seven reads of the full layer's K and V, each row up to its own position, through the decode_attend kernel."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('cross_attention',))
