"""Device time per training step of the held experts' grouped matmuls,
both directions: leaf ops under the program's ``moe_experts`` scope
(``nn/moe.py``)."""
from bench import layer_scopes


def read(ctx):
    return layer_scopes.ms_per_step(ctx, layer_scopes.MOE_EXPERTS)
