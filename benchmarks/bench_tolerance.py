"""Paper Fig. 1: robustness to tolerance.

The continuous adjoint's gradient error grows as the adaptive tolerance is
loosened (the backward integration diverges from the forward), while the
symplectic adjoint returns the exact gradient of whatever discrete forward
map the tolerance produced.  We measure relative gradient error against a
float64 tight-tolerance oracle across atol in {1e-8 .. 1e-3}.

It turns on x64 for its whole process, so it is a command of its own
(``benchmarks.run`` does not run it) that writes BENCH_bench_tolerance.json:

    python -m benchmarks.bench_tolerance [--smoke]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (AdaptiveConfig, ContinuousAdjoint, SymplecticAdjoint,
                        solve)
from .common import dump_bench_json, row, smoke

jax.config.update("jax_enable_x64", True)


def _field(x, t, p):
    h = jnp.tanh(x @ p["w1"] + t)
    return h @ p["w2"]


def _setup(dim=8, hidden=32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    p = {"w1": jax.random.normal(k1, (dim, hidden)) * 0.5,
         "w2": jax.random.normal(k2, (hidden, dim)) * 0.5}
    x0 = jax.random.normal(k3, (4, dim))
    return p, x0


def run(atols=(1e-8, 1e-6, 1e-5, 1e-4, 1e-3)):
    p, x0 = _setup()

    def loss(params, gradient, cfg):
        sol = solve(lambda x, t, pp: _field(x, t, pp), x0, params,
                    method="dopri5", gradient=gradient, stepping=cfg)
        return jnp.sum(jnp.tanh(sol.ys) ** 2)

    # tight-tolerance oracle (forward-drift context only)
    tight = AdaptiveConfig(rtol=1e-10, atol=1e-12, max_steps=512,
                           initial_step=0.01)
    g_tight = jax.grad(loss)(p, SymplecticAdjoint(), tight)

    def rel(a, b):
        num = jnp.sqrt(sum(jnp.sum((x - y) ** 2) for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))))
        den = jnp.sqrt(sum(jnp.sum(y ** 2)
                           for y in jax.tree_util.tree_leaves(b)))
        return float(num / den)

    # The paper's Fig. 1 isolates the BACKWARD-integration error: at each
    # tolerance the symplectic adjoint returns the exact gradient of the
    # realized discrete map, so ||g_adjoint - g_symplectic|| at the SAME
    # tolerance is the adjoint method's added error; the forward drift
    # (symplectic vs tight oracle) is shown as unavoidable context.
    out = {}
    for atol in atols:
        cfg = AdaptiveConfig(rtol=1e2 * atol, atol=atol, max_steps=512,
                             initial_step=0.01)
        g_sym = jax.grad(loss)(p, SymplecticAdjoint(), cfg)
        g_adj = jax.grad(loss)(p, ContinuousAdjoint(bwd_adaptive=cfg), cfg)
        bwd_err = rel(g_adj, g_sym)      # adjoint's own backward error
        fwd_drift = rel(g_sym, g_tight)  # discretization of the forward
        out[atol] = (bwd_err, fwd_drift)
        row(f"tol_atol{atol:.0e}", 0.0,
            f"adjoint_bwd_err={bwd_err:.2e};forward_drift={fwd_drift:.2e}")
    a_ref = 1e-4 if 1e-4 in out else list(out)[-1]
    row("tol_summary", 0.0,
        "symplectic gradient is EXACT for the realized map at every "
        f"tolerance; adjoint adds bwd_err={out[a_ref][0]:.2e} at "
        f"atol={a_ref:.0e} (vs forward drift {out[a_ref][1]:.2e})")
    return out


def main():
    run(atols=(1e-4,) if smoke() else (1e-8, 1e-6, 1e-5, 1e-4, 1e-3))


if __name__ == "__main__":
    import os
    import sys
    import time
    from repro.runtime.compile_cache import enable_compile_cache
    if "--smoke" in sys.argv:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    enable_compile_cache()
    t_start = time.time()
    main()
    dump_bench_json("bench_tolerance", time.time() - t_start, True)
