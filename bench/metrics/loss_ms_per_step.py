"""Device time of the LM head and cross entropy per training step, both
directions: leaf ops under the ``lm_loss`` scope (``train/losses.py``,
the head in ``models/lm.py``) and not under ``ode_solve``."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "loss")
