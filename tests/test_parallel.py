"""repro.parallel: mesh-sharded masked-batch solving.

Acceptance criteria pinned here (ISSUE 9):
  * ``solve(..., batch_axis=0, mesh=...)`` on (4,) and (2, 2) data meshes
    matches the single-device masked batch solve — each shard's lane block
    is BITWISE identical to a single-device solve of that block (values,
    per-lane stats, accepted grids, h carries), and the gathered result
    matches the full-width batch exactly on integer stats and to f64
    rounding on floats (the full-width grids themselves are batch-width-
    dependent XLA codegen, the test_batch.py precedent);
  * sharded gradients match unsharded ones to <= 1e-12 (f64) for the
    symplectic AND continuous adjoint, fixed AND adaptive stepping;
  * the collective-count rule proves the backward jaxpr all-reduces
    exactly the theta cotangents (one psum per param leaf) and nothing
    else, and the forward is collective-free;
  * ``batch_specs`` falls back to a divisible PREFIX of ("pod", "data")
    with a warning instead of silently replicating (B=6 on a (2, 2)
    mesh shards 2-way over "pod").

The spec/rule layer only reads ``mesh.shape`` / ``mesh.axis_names``, so it
is tested in-process against a duck-typed stand-in; everything needing
real multi-device execution goes through the ``run_sharded`` subprocess
fixture (tests/conftest.py) because the forced host-device flag must be
set before jax initializes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from jax.sharding import PartitionSpec as P

from repro.core import AdaptiveConfig, solve
from repro.core.stepper import AdaptiveStepper
from repro.core.tableau import get_tableau
from repro.launch.mesh import make_debug_mesh, make_lane_mesh
from repro.parallel import (batch_specs, batched_solution_specs, lane_axes,
                            lane_spec, make_sharder, param_specs,
                            shard_count, solver_state_specs, state_specs,
                            with_shard_load_stats)
from repro.serve.engine import EngineConfig


class _FakeMesh:
    """Duck-typed mesh: the spec layer reads only .shape / .axis_names, so
    divisibility and path rules are testable without real devices."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


# ---------------------------------------------------------------------------
# lane_axes: the divisible-prefix rule
# ---------------------------------------------------------------------------

def test_lane_axes_divisible_prefix():
    mesh = _FakeMesh(pod=2, data=2)
    assert lane_axes(mesh, 8) == ("pod", "data")
    with pytest.warns(UserWarning, match="divisible prefix"):
        assert lane_axes(mesh, 6) == ("pod",)
    with pytest.warns(UserWarning, match="replicated"):
        assert lane_axes(mesh, 5) == ()
    with pytest.raises(ValueError, match="Pad the batch"):
        lane_axes(mesh, 5, require=True)
    assert lane_axes(_FakeMesh(data=4), 8) == ("data",)
    assert lane_axes(_FakeMesh(data=4, model=2), 8) == ("data",)
    # a mesh with NO data axis can never satisfy require=True
    assert lane_axes(_FakeMesh(model=2), 8) == ()
    with pytest.raises(ValueError, match="none of the data axes"):
        lane_axes(_FakeMesh(model=2), 8, require=True)
    assert shard_count(mesh, ("pod", "data")) == 4
    assert shard_count(mesh, ()) == 1


def test_batch_specs_prefix_fallback():
    mesh = _FakeMesh(pod=2, data=2)
    batch = {"x": np.zeros((6, 3)), "y": np.zeros((8,)),
             "s": np.zeros(())}
    with pytest.warns(UserWarning, match="divisible prefix"):
        specs = batch_specs(batch, mesh)
    # B=6 on the (2, 2) mesh: 2-way over "pod", NOT silently replicated
    assert specs["x"] == P(("pod",), None)
    assert specs["y"] == P(("pod", "data"))
    assert specs["s"] == P()
    # nothing divides 5: replicate (still warned)
    with pytest.warns(UserWarning):
        specs5 = batch_specs({"x": np.zeros((5, 2))}, mesh)
    assert specs5["x"] == P(None, None)


# ---------------------------------------------------------------------------
# dormant spec layer: param/state path rules, make_sharder
# ---------------------------------------------------------------------------

def test_param_specs_path_rules():
    mesh = _FakeMesh(data=2, model=2)
    params = {"blk": {"wq": np.zeros((8, 8)), "wo": np.zeros((8, 8)),
                      "b1": np.zeros((8,)), "scale": np.zeros(())},
              "narrow": {"wq": np.zeros((8, 5))},
              "moe": {"wg": np.zeros((4, 8, 8))},
              "unit": {"wq": np.zeros((3, 8, 8))}}
    specs = param_specs(params, mesh)
    assert specs["blk"]["wq"] == P(None, "model")       # column-parallel
    assert specs["blk"]["wo"] == P("model", None)       # row-parallel
    assert specs["blk"]["b1"] == P(None)                # replicated
    assert specs["blk"]["scale"] == P()
    # non-divisible OUT dim (5 % 2): the model assignment is dropped
    assert specs["narrow"]["wq"] == P(None, None)
    # expert bank, TP-in-expert by default; EP shards the expert dim
    assert specs["moe"]["wg"] == P(None, None, "model")
    assert param_specs(params, mesh, ep=True)["moe"]["wg"] \
        == P("model", None, None)
    # vmap-stacked layer dim is never sharded
    assert specs["unit"]["wq"] == P(None, None, "model")
    # a data-only mesh has no "model" axis: everything replicates
    flat = jax.tree_util.tree_leaves(
        param_specs(params, _FakeMesh(data=4)))
    assert all(s == P() for s in flat)


def test_state_specs_zero1():
    mesh = _FakeMesh(data=2, model=2)
    p = {"wq": np.zeros((8, 8)), "tiny": np.zeros((3,))}
    state = {"params": p,
             "opt": {"step": np.zeros(()),
                     "m": {"wq": np.zeros((8, 8)),
                           "tiny": np.zeros((3,))}}}
    specs = state_specs(state, mesh)
    assert specs["params"]["wq"] == P(None, "model")
    assert specs["opt"]["step"] == P()
    # ZeRO-1: the m leaf takes "data" on the first unsharded divisible dim
    assert specs["opt"]["m"]["wq"] == P("data", "model")
    # ...but never a non-divisible one (3 % 2)
    assert specs["opt"]["m"]["tiny"] == P(None)
    assert state_specs(state, mesh, zero1=False)["opt"]["m"]["wq"] \
        == P(None, "model")


def test_make_sharder_none_mesh_is_identity():
    shard = make_sharder(None)
    x = jnp.ones((4, 4))
    assert shard(x, ("batch", "ffn")) is x


# ---------------------------------------------------------------------------
# solve-facing spec builders
# ---------------------------------------------------------------------------

def test_batched_solution_specs_layout():
    specs = batched_solution_specs(("data",))
    assert specs.x_final == P(("data",))
    assert specs.n_accepted == P(("data",))
    # step-major checkpoint stacks carry lanes on axis 1
    assert specs.ts == P(None, ("data",))
    assert specs.hs == P(None, ("data",))
    assert lane_spec((), 0) == P()
    assert lane_spec(("pod", "data"), 1) == P(None, ("pod", "data"))


def test_solver_state_specs_shape_aware():
    def field(x, t, p):
        return -x
    stepper = AdaptiveStepper(field, get_tableau("bosh3"),
                              AdaptiveConfig(max_steps=4), "jnp")
    batched = stepper.init_state(jnp.zeros((4, 2)), 0.0, 1.0, lanes=4,
                                 rtol=1e-6, atol=1e-8)
    specs = solver_state_specs(batched, ("data",))
    # the engine's horizons are PER-LANE (B,) arrays: they shard too
    assert specs.t0 == P(("data",))
    assert specs.rtol == P(("data",))
    assert specs.ts == P(None, ("data",))
    assert jax.tree_util.tree_leaves(specs.x)[0] == P(("data",))
    single = stepper.init_state(jnp.zeros((2,)), 0.0, 1.0)
    specs1 = solver_state_specs(single, ("data",))
    assert specs1.t0 == P()
    assert specs1.ts == P()          # (max_steps,) buffer: no lane axis
    assert specs1.rtol is None


def test_with_shard_load_stats():
    stats = with_shard_load_stats(
        {"n_steps": jnp.array([1, 2, 3, 5], jnp.int32)}, 2)
    np.testing.assert_array_equal(np.asarray(stats["shard_steps"]), [3, 8])
    assert float(stats["load_imbalance"]) == pytest.approx(8 / 5.5)
    assert stats["n_steps"].shape == (4,)


# ---------------------------------------------------------------------------
# api validation + device-count ergonomics
# ---------------------------------------------------------------------------

def _field(x, t, p):
    return jnp.tanh(x @ p["w"])


def test_solve_mesh_validation():
    params = {"w": jnp.eye(2) * 0.1}
    x0 = jnp.ones((4, 2))
    with pytest.raises(ValueError, match="batch_axis=0"):
        solve(_field, x0[0], params, stepping=AdaptiveConfig(max_steps=8),
              mesh=_FakeMesh(data=4))
    with pytest.raises(ValueError, match="requires mesh="):
        solve(_field, x0, params, stepping=AdaptiveConfig(max_steps=8),
              batch_axis=0, sharding="auto")


def test_engine_config_mesh_bucket_validation():
    mesh = _FakeMesh(data=4)
    with pytest.raises(ValueError, match="divisible by 4"):
        EngineConfig(buckets=(4, 6), mesh=mesh)
    EngineConfig(buckets=(4, 8), mesh=mesh)     # whole shards: fine


def test_debug_mesh_names_the_flag():
    need = len(jax.devices()) + 1
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count"):
        make_debug_mesh(need, 1)
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count"):
        make_lane_mesh((need,))


# ---------------------------------------------------------------------------
# the communication contract, jaxpr-level (1-way mesh: shard_map emits the
# same structure as an N-way one, so this runs in the single-device suite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy,stepping", [("symplectic", "adaptive"),
                                               ("adjoint", "adaptive"),
                                               ("symplectic", "fixed")])
def test_collective_contract(strategy, stepping):
    from repro.analysis.cases import sharded_solve_probe
    from repro.analysis.rules import collective_findings
    from repro.analysis.traversal import collective_eqns
    probe = sharded_solve_probe(strategy, stepping)
    assert collective_findings(probe["value"], "t", kind="value") == []
    assert collective_findings(probe["grad"], "t", kind="grad",
                               param_shapes=probe["param_shapes"]) == []
    # exactly one real psum per theta leaf, nothing else
    colls = collective_eqns(probe["grad"].jaxpr)
    assert sorted(s for n, _, shapes in colls for s in shapes) \
        == sorted(tuple(s) for s in probe["param_shapes"])
    assert all(n == "psum" for n, _, _ in colls)
    # and the rule actually bites when the expectation is wrong
    bad = collective_findings(probe["grad"], "t", kind="grad",
                              param_shapes=probe["param_shapes"] + [(7,)])
    assert bad and bad[0].severity == "error"


# ---------------------------------------------------------------------------
# multi-device numerics (subprocess: 8 forced host devices)
# ---------------------------------------------------------------------------

_PREAMBLE = r"""
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_enable_x64", True)
from repro.core import AdaptiveConfig, SaveAt, solve
from repro.launch.mesh import make_lane_mesh

B, dim, hidden = 8, 4, 8
k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
params = {"w1": jax.random.normal(k1, (dim, hidden)) * 0.3,
          "b1": jnp.zeros((hidden,)),
          "w2": jax.random.normal(k2, (hidden, dim)) * 0.3,
          "b2": jnp.zeros((dim,))}
def field(x, t, p):
    h = jnp.tanh(x @ p["w1"] + p["b1"] + t)
    return h @ p["w2"] + p["b2"]
# heterogeneous magnitudes -> heterogeneous per-lane accepted grids
x0 = jax.random.normal(k3, (B, dim)) * jnp.linspace(
    0.5, 3.0, B)[:, None]
cfg = AdaptiveConfig(rtol=1e-8, atol=1e-10, max_steps=96)
"""

_SOLVE_SCRIPT = _PREAMBLE + r"""
for mesh, n_shards in [(make_lane_mesh((4,)), 4),
                       (make_lane_mesh((2, 2)), 4)]:
    for grad, stepping in [("symplectic", cfg), ("adjoint", cfg),
                           ("symplectic", 12), ("adjoint", 12)]:
        ref = solve(field, x0, params, gradient=grad, stepping=stepping,
                    batch_axis=0)
        sol = solve(field, x0, params, gradient=grad, stepping=stepping,
                    batch_axis=0, mesh=mesh)
        # integer stats + success: exact vs the full-width batch
        for k in ("n_steps", "n_fevals", "n_attempts"):
            np.testing.assert_array_equal(np.asarray(sol.stats[k]),
                                          np.asarray(ref.stats[k]), k)
        np.testing.assert_array_equal(np.asarray(sol.success),
                                      np.asarray(ref.success))
        # values: f64 rounding vs the full-width batch (batch-width-
        # dependent XLA codegen; test_batch.py precedent)
        np.testing.assert_allclose(np.asarray(sol.ys), np.asarray(ref.ys),
                                   rtol=0, atol=1e-11)
        # load-imbalance metric: per-shard totals partition the lane sum
        ss = np.asarray(sol.stats["shard_steps"])
        assert ss.shape == (n_shards,)
        assert ss.sum() == np.asarray(sol.stats["n_steps"]).sum()
        assert float(sol.stats["load_imbalance"]) >= 1.0
        # shard-local exactness: each shard's lane block is BITWISE the
        # single-device solve of that block
        per = B // n_shards
        for s in range(n_shards):
            blk = solve(field, x0[s * per:(s + 1) * per], params,
                        gradient=grad, stepping=stepping, batch_axis=0)
            assert np.array_equal(np.asarray(blk.ys),
                                  np.asarray(sol.ys[s * per:(s + 1) * per]))
        # gradients: <= 1e-12 vs unsharded, both strategies, both steppings
        def loss(p, x, mesh_):
            kw = {"mesh": mesh_} if mesh_ is not None else {}
            s = solve(field, x, p, gradient=grad, stepping=stepping,
                      batch_axis=0, **kw)
            return jnp.sum(jnp.sin(s.ys) ** 2)
        g_ref = jax.grad(loss, argnums=(0, 1))(params, x0, None)
        g_sh = jax.grad(loss, argnums=(0, 1))(params, x0, mesh)
        for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                        jax.tree_util.tree_leaves(g_sh)):
            assert float(jnp.max(jnp.abs(a - b))) <= 1e-12, (grad, stepping)
        print("ok", dict(mesh.shape), grad,
              stepping if isinstance(stepping, int) else "adaptive")
print("PASS")
"""

_GRIDS_SCRIPT = _PREAMBLE + r"""
from jax.sharding import PartitionSpec as P
from repro.core.rk import rk_solve_adaptive_batched
from repro.core.tableau import get_tableau
from repro.parallel import batched_solution_specs

tab = get_tableau("dopri5")
mesh = make_lane_mesh((4,))

def drv(x0_, params_):
    return rk_solve_adaptive_batched(field, tab, x0_, 0.0, 1.0, params_,
                                     cfg)

sh = jax.jit(jax.shard_map(drv, mesh=mesh, in_specs=(P("data"), P()),
                           out_specs=batched_solution_specs(("data",)),
                           check_vma=False))(x0, params)
# every field of the sharded solution -- including accepted grids (ts, hs,
# xs) and the h carry -- is bitwise the jitted local solve of each lane
# block (shard_map's body compiles exactly the local program)
drv_j = jax.jit(drv)
for s in range(4):
    loc = drv_j(x0[2 * s:2 * s + 2], params)
    for name in loc._fields:
        for a, b in zip(jax.tree_util.tree_leaves(getattr(loc, name)),
                        jax.tree_util.tree_leaves(getattr(sh, name))):
            lane_ax = 1 if np.ndim(b) and b.shape[0] == cfg.max_steps \
                else 0
            blk = jax.lax.slice_in_dim(b, 2 * s, 2 * s + 2, axis=lane_ax)
            assert np.array_equal(np.asarray(a), np.asarray(blk)), \
                (s, name)
print("PASS")
"""

_SAVEAT_SCRIPT = _PREAMBLE + r"""
mesh = make_lane_mesh((4,))
ts = jnp.linspace(0.25, 1.0, 4)
for stepping in (cfg, 6):
    ref = solve(field, x0, params, saveat=SaveAt(ts=ts), stepping=stepping,
                batch_axis=0)
    sol = solve(field, x0, params, saveat=SaveAt(ts=ts), stepping=stepping,
                batch_axis=0, mesh=mesh)
    assert sol.ys.shape == (4, B, dim)
    np.testing.assert_allclose(np.asarray(sol.ys), np.asarray(ref.ys),
                               rtol=0, atol=1e-11)
    np.testing.assert_array_equal(np.asarray(sol.stats["n_steps"]),
                                  np.asarray(ref.stats["n_steps"]))
    np.testing.assert_allclose(np.asarray(sol.final_state),
                               np.asarray(ref.final_state), rtol=0,
                               atol=1e-11)
    def loss(p, mesh_):
        kw = {"mesh": mesh_} if mesh_ is not None else {}
        s = solve(field, x0, p, saveat=SaveAt(ts=ts), stepping=stepping,
                  batch_axis=0, **kw)
        return jnp.sum(jnp.sin(s.ys) ** 2)
    g_ref = jax.grad(loss)(params, None)
    g_sh = jax.grad(loss)(params, mesh)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_sh)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-12

# rank-0 param leaves are lifted to (1,) at the shard_map boundary
# (lift_scalar_params) — grads must still come back scalar AND exact
def sfield(x, t, p):
    return p["gain"] * jnp.tanh(x @ p["w"])
sparams = {"gain": jnp.float64(0.7), "w": params["w1"][:dim, :dim]}
for strat, stepping in (("symplectic", cfg), ("adjoint", 8)):
    def sloss(p, mesh_):
        kw = {"mesh": mesh_} if mesh_ is not None else {}
        return jnp.sum(solve(sfield, x0, p, gradient=strat,
                             stepping=stepping, batch_axis=0, **kw).ys ** 2)
    g_ref = jax.grad(sloss)(sparams, None)
    g_sh = jax.jit(lambda p: jax.grad(sloss)(p, mesh))(sparams)
    assert jnp.ndim(g_sh["gain"]) == 0, g_sh["gain"].shape
    for k in sparams:
        assert float(jnp.max(jnp.abs(g_ref[k] - g_sh[k]))) <= 1e-12, \
            (strat, k)
print("PASS")
"""

_ENGINE_SCRIPT = _PREAMBLE + r"""
from repro.core.tableau import get_tableau
from repro.serve.engine import EngineConfig, Request, SolveEngine

tab = get_tableau("dopri5")
reqs = [Request(x0[i % B], 0.0, 0.5 + 0.05 * i, 1e-6 * (1 + i % 3), 1e-8)
        for i in range(10)]
mesh = make_lane_mesh((4,))
res = {}
for ecfg in (EngineConfig(buckets=(4, 8), mesh=mesh),
             EngineConfig(buckets=(4, 8))):
    eng = SolveEngine(field, tab, cfg, params, x0[0], ecfg)
    res[ecfg.mesh is not None] = (eng.run(list(reqs)), eng)
sharded, eng_s = res[True]
plain, _ = res[False]
assert set(sharded) == set(plain)
for rid in sharded:
    a, b = sharded[rid], plain[rid]
    assert (a.succeeded, a.n_accepted, a.n_fevals) \
        == (b.succeeded, b.n_accepted, b.n_fevals), rid
    assert float(jnp.max(jnp.abs(a.x_final - b.x_final))) <= 1e-12, rid
# the resident slot state actually lives lane-sharded on the mesh: lane
# fields on axis 0, step-major buffers on axis 1
t_spec = eng_s._state.t.sharding.spec
ts_spec = eng_s._state.ts.sharding.spec
assert "data" in str(t_spec), t_spec
assert len(ts_spec) >= 2 and ts_spec[0] is None \
    and "data" in str(ts_spec[1]), ts_spec
print("PASS")
"""

_SHARDER_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_debug_mesh
from repro.parallel import make_sharder

mesh = make_debug_mesh(2, 2)          # ("data", "model")
shard = jax.jit(lambda x: make_sharder(mesh)(x, ("batch", "ffn")))
y = shard(jnp.ones((4, 8)))
assert "data" in str(y.sharding.spec) and "model" in str(y.sharding.spec)
# non-divisible dims are never constrained (trailing Nones may be
# normalized away by the sharding layer)
y6 = jax.jit(lambda x: make_sharder(mesh)(x, ("batch", "ffn")))(
    jnp.ones((4, 5)))
spec6 = y6.sharding.spec
assert len(spec6) < 2 or spec6[1] is None, spec6
print("PASS")
"""


@pytest.mark.parametrize("script", [_SOLVE_SCRIPT, _GRIDS_SCRIPT,
                                    _SAVEAT_SCRIPT, _ENGINE_SCRIPT,
                                    _SHARDER_SCRIPT],
                         ids=["solve", "grids", "saveat", "engine",
                              "sharder"])
def test_multidevice(run_sharded, script):
    assert "PASS" in run_sharded(script, devices=8)
