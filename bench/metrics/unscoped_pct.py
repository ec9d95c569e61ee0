"""Share of the traced window's leaf-op device time under none of the
``ode_solve``, ``lm_loss`` and ``optimizer`` scopes, or on ops the
compiled step's name map lacks: what the phase metrics cannot place."""
from bench import scopes


def read(ctx):
    return scopes.unscoped_pct(ctx)
