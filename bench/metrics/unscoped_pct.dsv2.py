"""Share of the traced window's leaf-op device time under none of the
``ode_solve``, ``lm_loss``, ``optimizer`` and ``prefix_layers`` scopes, or
on ops the compiled step's name map lacks: ``unscoped_pct`` for a step
whose layers before the depth solve are a part of their own (read by
``prefix_ms_per_step``), with the grouped matmuls the compiler leaves
without a name stack counted under ``moe_experts``
(``bench/layer_scopes.py``)."""
from bench import layer_scopes


def read(ctx):
    return layer_scopes.unscoped_pct(ctx)
