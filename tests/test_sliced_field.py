"""A field that declares the one parameter slice it reads (``SlicedField``)
gets the symplectic adjoint's per-slice backward: each stage's VJP is taken
with respect to that slice and added into the gradient in place.

* Exactness: the sliced gradient equals the dense path's (the same field
  with the declaration stripped) and ``jax.grad`` through backprop, to
  rounding — euler and rk4 on an R-step grid (rk4's last stage reads the
  next layer), two steps a layer, the SaveAt path, and a toy field under
  an adaptive tableau (the ``live`` branch of the adaptive drivers).
* Engagement: the compiled backward loop of the smoke LM holds no
  broadcast, add or select of a stacked ``(R, ...)`` parameter shape; the
  dense path does, so the check tells the two apart.
* Fields called as plain callables (remat, backprop) trace the same
  program with or without the declaration.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_arch
from repro.configs.base import NodeConfig
from repro.core import AdaptiveConfig, SaveAt, SlicedField, solve
from repro.data.tokens import synthetic_lm_batch
from repro.models import lm
from repro.train import TrainConfig, init_train_state, make_grad_fn

R = 5   # no batch, sequence or width of the smoke LM equals it
TCFG = TrainConfig(loss_chunk=8)


def _arch(method="euler", n_steps=0, grad_mode="symplectic"):
    return get_smoke_arch("qwen3-0.6b").with_(
        n_layers=R, use_pallas=False,
        node=NodeConfig(mode="node", method=method, n_steps=n_steps,
                        grad_mode=grad_mode, combine_backend="jnp"))


def _strip(field):
    """The same field as a plain callable: no slice declared."""
    return lambda x, t, p: field(x, t, p)


@pytest.fixture
def dense_depth_field(monkeypatch):
    declared = lm._depth_field
    monkeypatch.setattr(lm, "_depth_field",
                        lambda cfg, shard: _strip(declared(cfg, shard)))


def _close(got, want, rel):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        scale = max(float(jnp.max(jnp.abs(w))), 1e-30)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=0, atol=rel * scale)


def _params():
    return init_train_state(jax.random.PRNGKey(0), _arch(), TCFG).params


def _batch():
    return {k: jnp.asarray(a) for k, a in
            synthetic_lm_batch(0, 2, 9, _arch().vocab).items()}


def _lm_grads(arch, params, batch):
    return jax.jit(make_grad_fn(arch, TCFG))(params, batch)[0]


def _depth_states_grads(arch, params, batch):
    depths = jnp.asarray([0.4, 1.0])

    def loss(p):
        x = lm._embed(p, arch, batch["tokens"], None, lm.no_shard)
        return jnp.sum(jnp.sin(lm.node_depth_states(p, arch, x, depths)))

    return jax.jit(jax.grad(loss))(params)


LM_CASES = {
    "euler-R": (dict(method="euler"), _lm_grads),
    "rk4-R": (dict(method="rk4"), _lm_grads),
    "euler-2R": (dict(method="euler", n_steps=2 * R), _lm_grads),
    "depth-states": (dict(method="euler"), _depth_states_grads),
}


@pytest.mark.parametrize("case", list(LM_CASES))
def test_sliced_lm_gradient_is_exact(case, request):
    node, grads = LM_CASES[case]
    params, batch = _params(), _batch()
    sliced = grads(_arch(**node), params, batch)
    backprop = grads(_arch(**node, grad_mode="backprop"), params, batch)
    request.getfixturevalue("dense_depth_field")
    dense = grads(_arch(**node), params, batch)
    _close(sliced, dense, 1e-5)
    _close(sliced, backprop, 2e-5)


def _toy_field():
    def index(t):
        return jnp.clip(jnp.floor(t * R).astype(jnp.int32), 0, R - 1)

    def apply(x, t, p):
        return jnp.tanh(x @ p["w"] + p["b"]) - 0.5 * x

    return SlicedField(index, apply)


def _toy_params():
    kw, kb = jax.random.split(jax.random.PRNGKey(1))
    return {"w": 0.6 * jax.random.normal(kw, (R, 4, 4), jnp.float32),
            "b": 0.3 * jax.random.normal(kb, (R, 4), jnp.float32)}


@pytest.mark.parametrize("saveat", [SaveAt(t1=1.0),
                                    SaveAt(ts=jnp.asarray([0.3, 0.7, 1.0]))],
                         ids=["t1", "ts"])
def test_sliced_field_adaptive_gradient_matches_dense(saveat):
    x0 = jnp.asarray([0.5, -0.3, 0.8, 0.1], jnp.float32)
    cfg = AdaptiveConfig(rtol=1e-4, atol=1e-6, max_steps=64)

    def grad(field):
        def loss(p):
            ys = solve(field, x0, p, saveat=saveat, method="dopri5",
                       stepping=cfg, backend="jnp").ys
            return jnp.sum(ys ** 2)
        return jax.jit(jax.grad(loss))(_toy_params())

    field = _toy_field()
    sliced = grad(field)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(sliced))
    # every layer of the stack gets a gradient
    assert bool(jnp.all(jnp.any(sliced["w"] != 0, axis=(1, 2))))
    _close(sliced, grad(_strip(field)), 1e-5)


@pytest.mark.parametrize("grad_mode", ["remat_step", "backprop"])
def test_plain_callers_trace_the_same_program(grad_mode):
    field = _toy_field()
    x0 = jnp.asarray([0.5, -0.3, 0.8, 0.1], jnp.float32)

    def jaxpr(f):
        def loss(p):
            return jnp.sum(solve(f, x0, p, method="rk4", gradient=grad_mode,
                                 stepping=2 * R, backend="jnp").ys)
        return str(jax.make_jaxpr(jax.grad(loss))(_toy_params()))

    assert jaxpr(field) == jaxpr(_strip(field))


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[^\s=]+ = (\S+?)(?:\{[^}\s]*\})? ([a-z][\w\-]*)\(")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_CALLEES = re.compile(
    r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def _stacked_ops_in_loop_bodies(text, stacked_shapes):
    """Broadcasts, adds and selects of a stacked parameter shape inside
    any while-loop body, or a computation such a body calls."""
    comps, comp = {}, None
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c and not line.startswith(" "):
            comp = comps.setdefault(c.group(1), [])
        elif comp is not None:
            comp.append(line)
    todo = [b for lines in comps.values() for line in lines
            for b in _BODY.findall(line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for one, many in _CALLEES.findall(line):
                todo += [one] if one else re.findall(r"[\w.\-]+", many)
    found = []
    for name in sorted(seen):
        for line in comps[name]:
            m = _INSTRUCTION.match(line)
            if m and m.group(1) in stacked_shapes and \
                    m.group(2) in ("broadcast", "add", "select"):
                found.append((name, line.strip()[:120]))
    return found


@pytest.mark.parametrize("path", ["sliced", "dense"])
def test_backward_loop_has_no_stacked_parameter_sums(path, request):
    if path == "dense":
        request.getfixturevalue("dense_depth_field")
    arch = _arch()
    params = jax.eval_shape(_params)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(make_grad_fn(arch, TCFG)).lower(
        params, {"tokens": tokens, "labels": tokens}).compile().as_text()
    stacked = {f"f32[{','.join(map(str, leaf.shape))}]"
               for leaf in jax.tree_util.tree_leaves(params["unit"])}
    assert all(s.startswith(f"f32[{R},") for s in stacked)
    found = _stacked_ops_in_loop_bodies(text, stacked)
    if path == "sliced":
        assert not found, found
    else:
        assert found
