"""Device time per training step of the expert layers' routing, both
directions: leaf ops under the program's ``moe_route`` scope
(``nn/moe.py``: router, top-k, balance loss, sort, gather and scatter)."""
from bench import layer_scopes


def read(ctx):
    return layer_scopes.ms_per_step(ctx, layer_scopes.MOE_ROUTE)
