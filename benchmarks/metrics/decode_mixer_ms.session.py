"""Device ms a decode step in the Mamba layers' mixer whole: norm, projections, convolution, the state's step, gate and residual."""
from benchmarks import inside_parts


def read(obs):
    return inside_parts.part_ms(obs, ('mamba_mixer', 'ssm_step'))
