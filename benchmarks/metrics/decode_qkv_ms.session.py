"""Device ms a decode step in the attention and cross layers' projections: norm, q (k and v where the layer has them), their biases, the widening of the paired heads; and the output projection with the pairs' difference and norm."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('qkv', 'attn_out'))
