"""Device time per training step of the layers that run before the depth
solve (DeepSeek's dense first layer), both directions: leaf ops under the
program's ``prefix_layers`` scope (``models/lm.py``)."""
from bench import layer_scopes


def read(ctx):
    return layer_scopes.ms_per_step(ctx, layer_scopes.PREFIX_LAYERS)
