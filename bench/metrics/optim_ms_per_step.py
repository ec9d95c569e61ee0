"""Device time of the global-norm clip and AdamW per training step: leaf
ops under the ``optimizer`` scope (``optim/clip.py``, ``optim/adamw.py``)
and under neither ``ode_solve`` nor ``lm_loss``."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "optim")
