"""Device ms a decode step in the embedding's lookup, the final norm, the unembedding over the vocabulary and the pick."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('embed', 'head'))
