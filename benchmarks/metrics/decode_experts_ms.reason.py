"""Device ms a decode step in the feed-forward norm, the router and the held experts' tile loop with its residual."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('router', 'experts'))
