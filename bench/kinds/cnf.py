"""Continuous normalizing flows (FFJORD) trained by maximum likelihood:
``models/cnf.py``'s augmented field (state, log-density change, Hutchinson
noise) solved by adaptive dopri5 through ``repro.core.solve`` with a
gradient strategy, and the program's AdamW (``repro.optim``).

The traffic's ``controller`` picks the step controller.  ``lockstep`` (the
default) is one controller for the whole batch: the timed step is
``value_and_grad`` of the public ``cnf_nll`` and the AdamW update.
``per_sample`` gives every sample a controller of its own, as lanes sharded
over a data mesh of the cell's chips: the timed step composes
``cnf_nll``'s parts (``models/per_sample.py: model_solve_ys`` over the
augmented field) with ``mesh=`` handed to ``solve(..., batch_axis=0)``,
since ``cnf_nll`` takes no mesh.  Each chip then solves and replays its
own lanes, and the parameter gradient is summed across chips.

``reference_readings`` is the plain reference: the same concatsquash field,
Hutchinson estimate and dopri5 controller written here, the accepted grid
found by a plain while loop (one per sample for ``per_sample``, vmapped),
and the gradient by ``jax.grad`` through a replay of the accepted steps with
their sizes held fixed (the gradient of the discrete map, which the exact
adjoints compute).  It imports nothing from the program.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import compare, counts, generate

# Dormand-Prince 5(4): stage weights, solution weights, error weights
# (solution minus embedded 4th-order weights).
DP_C = (0.0, 1 / 5, 3 / 10, 4 / 5, 8 / 9, 1.0, 1.0)
DP_A = ((),
        (1 / 5,),
        (3 / 40, 9 / 40),
        (44 / 45, -56 / 15, 32 / 9),
        (19372 / 6561, -25360 / 2187, 64448 / 6561, -212 / 729),
        (9017 / 3168, -355 / 33, 46732 / 5247, 49 / 176, -5103 / 18656),
        (35 / 384, 0.0, 500 / 1113, 125 / 192, -2187 / 6784, 11 / 84))
DP_B = (35 / 384, 0.0, 500 / 1113, 125 / 192, -2187 / 6784, 11 / 84, 0.0)
DP_BSTAR = (5179 / 57600, 0.0, 7571 / 16695, 393 / 640, -92097 / 339200,
            187 / 2100, 1 / 40)
DP_E = tuple(b - bs for b, bs in zip(DP_B, DP_BSTAR))
ERR_ORDER = 4
# the controller's constants (repro.core.AdaptiveConfig's defaults)
SAFETY, MIN_FACTOR, MAX_FACTOR, H0, MAX_ATTEMPTS = 0.9, 0.2, 10.0, 0.01, 4096


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def weights(key, cfg: dict) -> list:
    """PyTorch ``Linear`` init, U(-1/sqrt(fan_in), 1/sqrt(fan_in)), for
    every layer's W, b and the time gate and bias (fan_in 1)."""
    dims = [cfg["dim"], *cfg["hidden"], cfg["dim"]]
    layers = []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        ks = jax.random.split(k, 4)
        n_in, n_out = dims[i], dims[i + 1]
        lim = n_in ** -0.5
        u = functools.partial(jax.random.uniform, dtype=jnp.float32)
        layers.append({
            "w": u(ks[0], (n_in, n_out), minval=-lim, maxval=lim),
            "b": u(ks[1], (n_out,), minval=-lim, maxval=lim),
            "wt_gate": u(ks[2], (1, n_out), minval=-1.0, maxval=1.0),
            "wt_bias": u(ks[3], (1, n_out), minval=-1.0, maxval=1.0)})
    return layers


def leaf_values(layers) -> dict:
    return {f"L{i}.{k}": float(v) for i, lp in enumerate(layers)
            for k, v in lp.items()}


def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def _field(layers, x, t):
    h = x
    for i, lp in enumerate(layers):
        h = (h @ lp["w"]) * jax.nn.sigmoid(t * lp["wt_gate"]) + lp["b"] \
            + t * lp["wt_bias"]
        if i < len(layers) - 1:
            h = jnp.tanh(h)
    return h


def _aug(layers, state, t):
    x, _, e = state
    fx, vjp = jax.vjp(lambda xx: _field(layers, xx, t), x)
    (etj,) = vjp(e)
    return (fx, -jnp.sum(etj * e, axis=-1), jnp.zeros_like(e))


def _axpy(x, ks, coefs, h):
    out = x
    for c, k in zip(coefs, ks):
        if c != 0.0:
            out = jax.tree_util.tree_map(lambda o, kk: o + (h * c) * kk,
                                         out, k)
    return out


def _dopri5(layers, x, t, h):
    """One step: (solution, error estimate)."""
    ks = []
    for i in range(7):
        xi = _axpy(x, ks, DP_A[i], h)
        ks.append(_aug(layers, xi, t + DP_C[i] * h))
    sol = _axpy(x, ks, DP_B, h)
    zero = jax.tree_util.tree_map(jnp.zeros_like, x)
    return sol, _axpy(zero, ks, DP_E, h)


def _error_norm(err, x, x_next, rtol, atol):
    total, count = 0.0, 0
    for e, a, b in zip(jax.tree_util.tree_leaves(err),
                       jax.tree_util.tree_leaves(x),
                       jax.tree_util.tree_leaves(x_next)):
        r = e / (atol + rtol * jnp.maximum(jnp.abs(a), jnp.abs(b)))
        total = total + jnp.sum(r * r)
        count += r.size
    return jnp.sqrt(total / count)


def _accepted_grid(layers, x0, cfg):
    """Run the PI controller; returns (ts, hs, n_accepted, reached t1)."""
    n_max, t1 = cfg["max_steps"], cfg["t1"]
    t_res = 4.0 * float(np.finfo(np.float32).eps) * max(abs(t1), 1.0)

    def active(c):
        t, x, h, n, att, ts, hs = c
        return (t1 - t > t_res) & (n < n_max) & (att < MAX_ATTEMPTS) \
            & jnp.isfinite(h)

    def body(c):
        t, x, h, n, att, ts, hs = c
        clamped = jnp.abs(h) > t1 - t
        h_eff = jnp.minimum(h, t1 - t)
        x_next, err = _dopri5(layers, x, t, h_eff)
        enorm = _error_norm(err, x, x_next, cfg["rtol"], cfg["atol"])
        accept = enorm <= 1.0
        factor = jnp.clip(SAFETY * jnp.maximum(enorm, 1e-10)
                          ** (-1.0 / (ERR_ORDER + 1)), MIN_FACTOR, MAX_FACTOR)
        h_new = jnp.where(accept & clamped, h, h * factor)
        ts = jnp.where(accept, ts.at[n].set(t), ts)
        hs = jnp.where(accept, hs.at[n].set(h_eff), hs)
        x = jax.tree_util.tree_map(lambda a, b: jnp.where(accept, b, a), x,
                                   x_next)
        t = jnp.where(accept, t + h_eff, t)
        return (t, x, h_new, n + accept.astype(jnp.int32), att + 1, ts, hs)

    z = jnp.zeros((n_max,), jnp.float32)
    c = (jnp.float32(0.0), x0, jnp.float32(H0), jnp.int32(0), jnp.int32(0),
         z, z)
    t, _, _, n, _, ts, hs = jax.lax.while_loop(active, body, c)
    return ts, hs, n, t1 - t <= t_res


def _replay_nll(layers, u, eps, ts, hs, n, reached, cfg):
    """NLL of each row through the accepted steps, sizes held fixed."""
    state = (u, jnp.zeros(u.shape[0], u.dtype), eps)

    @jax.checkpoint
    def body(s, k):
        return jax.lax.cond(k < n, lambda s: _dopri5(layers, s, ts[k],
                                                     hs[k])[0],
                            lambda s: s, s), None

    (z, dlp, _), _ = jax.lax.scan(body, state, jnp.arange(cfg["max_steps"]))
    logpz = -0.5 * jnp.sum(z * z, -1) - 0.5 * cfg["dim"] * jnp.log(2 * jnp.pi)
    nll = -(logpz - dlp)
    return jnp.where(reached, nll, jnp.nan)


def _mean_nll(layers, u, eps, cfg):
    """Mean NLL of a batch under one controller, and the rows' NLL."""
    ts, hs, n, ok = _accepted_grid(
        jax.lax.stop_gradient(layers),
        (u, jnp.zeros(u.shape[0], u.dtype), eps), cfg)
    nll = _replay_nll(layers, u, eps, ts, hs, n, ok, cfg)
    return jnp.mean(nll), nll


def _lanes_mean_nll(layers, u, eps, cfg):
    """Mean NLL of a batch with a controller of its own for every sample,
    and the rows' NLL."""
    def one(u1, e1):
        x0 = (u1[None], jnp.zeros((1,), u1.dtype), e1[None])
        ts, hs, n, ok = _accepted_grid(jax.lax.stop_gradient(layers), x0,
                                       cfg)
        return _replay_nll(layers, u1[None], e1[None], ts, hs, n, ok,
                           cfg)[0]

    nll = jax.vmap(one)(u, eps)
    return jnp.mean(nll), nll


def _per_sample(traffic: dict) -> bool:
    """Does the traffic give every sample a step controller of its own?"""
    controller = traffic.get("controller", "lockstep")
    if controller not in ("lockstep", "per_sample"):
        raise ValueError(f"controller {controller!r}: lockstep or "
                         "per_sample")
    return controller == "per_sample"


def _adamw(opt, params, grads, m, v, t):
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v,
                               grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2)
                                                     + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v


def reference_readings(cfg: dict, traffic: dict, seed: int,
                       devices=None) -> dict:
    """Losses, first gradient and parameter change of the first
    ``compare_steps`` steps, in float32 at "highest".  With a controller
    for every sample, the samples are spread over ``devices`` (default:
    JAX's default device), which the samples' independence allows."""
    B = traffic["batch"]
    mix = generate.gaussian_mixture(traffic["mixture_seed"], cfg["dim"],
                                   traffic["mixture"])
    mean_nll = _lanes_mean_nll if _per_sample(traffic) else _mean_nll
    put, placed = jnp.asarray, {}
    if _per_sample(traffic) and devices:
        mesh = Mesh(np.asarray(devices), ("data",))
        put = functools.partial(jax.device_put,
                                device=NamedSharding(mesh, P("data")))
        placed = {"out_shardings": NamedSharding(mesh, P())}
    with jax.default_matmul_precision("highest"):
        make = jax.jit(lambda k: weights(k, cfg), **placed)
        grad_fn = jax.jit(jax.grad(functools.partial(mean_nll, cfg=cfg),
                                   has_aux=True))
        step_fn = jax.jit(functools.partial(_adamw, cfg["train"]))
        key = jnp.asarray(generate.seed_words(seed))
        params = make(key)
        m = v = jax.tree_util.tree_map(jnp.zeros_like, params)
        out = {"loss": []}
        for t in range(traffic["compare_steps"]):
            b = generate.mixture_batch(seed, t, mix, B)
            g, nll = grad_fn(params, put(b["u"]), put(b["eps"]))
            out["loss"].append(float(jnp.mean(nll)))
            if t == 0:
                out["grad"] = leaf_values(jax.device_get(_norms(g)))
            params, m, v = step_fn(params, g, m, v, jnp.float32(t + 1))
        diff = jax.tree_util.tree_map(jnp.subtract, params, make(key))
        out["update"] = leaf_values(jax.device_get(_norms(diff)))
    return out


# --------------------------------------------------------------------------
# the job the harness drives
# --------------------------------------------------------------------------

class Job:
    def __init__(self, cell, devices, seed: int):
        from repro.core import AdaptiveConfig, SaveAt, as_gradient, solve
        from repro.models.cnf import CNFConfig, _aug_field_hutch, cnf_nll
        from repro.models.per_sample import model_solve_ys
        from repro.optim import AdamWConfig, adamw_init, adamw_update

        cfg, tr = cell.config, cell.traffic
        self.cfg, self.traffic, self.seed = cfg, tr, seed
        self.devices = devices
        self.warm_steps = tr["compare_steps"]
        self.trace_steps = tr["trace_steps"]
        self.limits = tr["limits"]
        # one data set for every seed, which draws the batches from it: the
        # adaptive solver's work follows the data
        self.mix = generate.gaussian_mixture(tr["mixture_seed"], cfg["dim"],
                                             tr["mixture"])
        opt = cfg["train"]
        self.adamw_cfg = adamw_cfg = AdamWConfig(
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"])
        per_sample = _per_sample(tr)
        self.ccfg = ccfg = CNFConfig(
            dim=cfg["dim"], hidden=tuple(cfg["hidden"]),
            n_components=cfg["n_components"], t1=cfg["t1"],
            trace=cfg["trace"], method=cfg["method"],
            grad_mode=tr["gradient"], combine_backend=cfg["combine_backend"],
            adaptive=True, rtol=cfg["rtol"], atol=cfg["atol"],
            max_steps=cfg["max_steps"], per_sample=per_sample)
        precision = cfg["matmul_precision"]
        stepping = AdaptiveConfig(rtol=cfg["rtol"], atol=cfg["atol"],
                                  max_steps=cfg["max_steps"])
        solve_kw = dict(saveat=SaveAt(t1=cfg["t1"]), method=cfg["method"],
                        gradient=as_gradient(tr["gradient"]),
                        stepping=stepping, backend=cfg["combine_backend"])

        if per_sample:
            # lanes over a data mesh of the cell's chips; parameters and
            # optimizer state replicated
            self.mesh = Mesh(np.asarray(devices), ("data",))
            self.rows = NamedSharding(self.mesh, P("data"))
            placed = {"out_shardings": NamedSharding(self.mesh, P())}

            def nll(params, u, eps):
                """``cnf_nll`` of a per-sample config, with the mesh."""
                def body(carry, comp):
                    x, dlp = carry
                    x, dlp_i, _ = model_solve_ys(
                        _aug_field_hutch, (x, jnp.zeros_like(dlp), eps),
                        comp, per_sample=True, mesh=self.mesh, **solve_kw)
                    return (x, dlp + dlp_i), None

                (z, dlp), _ = jax.lax.scan(
                    body, (u, jnp.zeros(u.shape[0], u.dtype)),
                    params["components"])
                logpz = -0.5 * jnp.sum(z ** 2, -1) - \
                    0.5 * cfg["dim"] * jnp.log(2 * jnp.pi)
                return -jnp.mean(logpz - dlp)
        else:
            self.mesh = self.rows = None
            placed = {}

            def nll(params, u, eps):
                return cnf_nll(params, u, eps, ccfg)

        def init(key):
            comps = jax.tree_util.tree_map(lambda l: l[None],
                                           weights(key, cfg))
            params = {"components": comps}
            return {"params": params, "opt": adamw_init(params, adamw_cfg)}

        def step(state, u, eps):
            with jax.default_matmul_precision(precision):
                loss, g = jax.value_and_grad(nll)(state["params"], u, eps)
                params, opt_state = adamw_update(state["params"], g,
                                                 state["opt"], opt["lr"],
                                                 adamw_cfg)
            return {"params": params, "opt": opt_state}, loss

        def solver_stats(params, u, eps):
            """The forward solve's stats: attempted and accepted steps,
            per sample with a controller each (and then the shards' load)."""
            comp = jax.tree_util.tree_map(lambda l: l[0],
                                          params["components"])
            state = (u, jnp.zeros(u.shape[0], u.dtype), eps)
            lanes = {}
            if per_sample:
                state = jax.tree_util.tree_map(lambda l: l[:, None], state)
                lanes = {"batch_axis": 0, "mesh": self.mesh}
            with jax.default_matmul_precision(precision):
                sol = solve(_aug_field_hutch, state, comp, **solve_kw,
                            **lanes)
            return sol.stats

        self.key = jnp.asarray(generate.seed_words(seed))
        self.state = jax.jit(init, **placed)(self.key)
        self.step_fn = jax.jit(step, donate_argnums=0)
        self.nll = nll
        self.solver_stats = jax.jit(solver_stats)
        self.readings = {"loss": []}
        self._batch = None
        self._recent = collections.deque(maxlen=self.trace_steps)

    def _batch_of(self, i: int) -> tuple:
        b = generate.mixture_batch(self.seed, i, self.mix,
                                   self.traffic["batch"])
        if self.rows is not None:
            return tuple(jax.device_put((b["u"], b["eps"]), self.rows))
        return jnp.asarray(b["u"]), jnp.asarray(b["eps"])

    def step(self, i: int) -> float:
        with jax.profiler.TraceAnnotation("bench.batch"):
            u, eps = self._batch_of(i)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.state, loss = self.step_fn(self.state, u, eps)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            loss = float(loss)
        self._batch = (u, eps)
        self._recent.append(i)
        return loss

    def _recent_stats(self) -> list:
        """The forward solve's stats, at the current parameters, on the
        batches of the last steps run."""
        return [jax.device_get(self.solver_stats(self.state["params"],
                                                 *self._batch_of(i)))
                for i in self._recent]

    def flops_per_step(self) -> float:
        """Model FLOPs of a step: every attempted step of the forward solve
        (7 evaluations of the augmented field) and the backward of every
        accepted one (twice an evaluation), of every sample, recomputation
        not counted; with a controller per sample, each sample's own
        counts.  The solver's counts are read, at the current parameters,
        on the batches of the last steps run."""
        B = self.traffic["batch"]
        f_eval = counts.cnf_field_flops(self.cfg["dim"], self.cfg["hidden"],
                                        1)
        total = 0.0
        for st in self._recent_stats():
            work = np.broadcast_to(
                np.asarray(st["n_attempts"], np.int64)
                + 2 * np.asarray(st["n_steps"], np.int64), (B,))
            total += 7 * f_eval * int(work.sum())
        return total / len(self._recent)

    def load_imbalance(self):
        """Max over mean of the shards' accepted steps
        (``parallel/solve.py: with_shard_load_stats``), the mean over the
        batches of the last steps run; None off a mesh."""
        if self.mesh is None:
            return None
        return float(np.mean([float(st["load_imbalance"])
                              for st in self._recent_stats()]))

    def _layers(self, tree):
        return [jax.tree_util.tree_map(lambda l: l[0], lp)
                for lp in tree["components"]]

    def after_warm_step(self, i: int, loss: float) -> None:
        self.readings["loss"].append(loss)
        if i == 0:
            b1 = self.cfg["train"]["b1"]
            g = jax.tree_util.tree_map(lambda m: m / (1 - b1),
                                       self.state["opt"]["m"])
            self.readings["grad"] = leaf_values(jax.device_get(
                _norms(self._layers(g))))
        if i == self.warm_steps - 1:
            init = weights(self.key, self.cfg)
            diff = jax.tree_util.tree_map(
                jnp.subtract, self._layers(self.state["params"]), init)
            self.readings["update"] = leaf_values(jax.device_get(
                _norms(diff)))

    def grad_peak_bytes(self) -> int:
        """Compiler's peak of ``value_and_grad`` of the NLL alone."""
        precision = self.cfg["matmul_precision"]

        def vg(params, u, eps):
            with jax.default_matmul_precision(precision):
                return jax.value_and_grad(self.nll)(params, u, eps)

        exe = jax.jit(vg).lower(self.state["params"], *self._batch).compile()
        return int(exe.memory_analysis().peak_memory_in_bytes)

    def release(self) -> None:
        self.state = None
        self._batch = None

    def check(self) -> dict:
        ref = reference_readings(self.cfg, self.traffic, self.seed,
                                 self.devices)
        return compare.compare_training(self.readings, ref, self.limits)


def build(cell, devices, seed: int) -> Job:
    return Job(cell, devices, seed)


# --------------------------------------------------------------------------
# the control and the faults (bench/calibrate.py, tests/bench)
# --------------------------------------------------------------------------

# the configuration's own knob one precision below "highest": "high", three
# bfloat16 passes
CONTROL = {"matmul_precision": "high"}
FAULTS = ("unchanged", "half_batch")


def faults(cell) -> tuple:
    """The faults ``plant`` can put into a job of ``cell``: with lanes
    sharded over chips, also ``no_exchange``."""
    return FAULTS + (("no_exchange",) if _per_sample(cell.traffic) else ())


def plant(job: Job, fault: str) -> None:
    """Break the job's timed step: ``unchanged`` returns the state as it
    came; ``half_batch`` drops the second half of every batch, so the mean
    is taken over the rest; ``no_exchange`` leaves out the sum of the
    gradient across chips, so that each chip steps with the gradient of its
    own lanes (of the batch's mean NLL)."""
    from repro.models.cnf import cnf_nll
    from repro.optim import adamw_update
    opt = job.cfg["train"]
    precision = job.cfg["matmul_precision"]
    B = job.traffic["batch"]
    if fault == "no_exchange" and job.mesh is not None:
        def local(state, u, eps):
            def nll(params):
                return cnf_nll(params, u, eps, job.ccfg) * (u.shape[0] / B)

            with jax.default_matmul_precision(precision):
                loss, g = jax.value_and_grad(nll)(state["params"])
                params, o = adamw_update(state["params"], g, state["opt"],
                                         opt["lr"], job.adamw_cfg)
            return {"params": params, "opt": o}, jax.lax.psum(loss, "data")

        job.step_fn = jax.jit(jax.shard_map(
            local, mesh=job.mesh, in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P()), check_vma=False))
        return
    if fault not in FAULTS:
        raise ValueError(fault)
    rows = B // (2 if fault == "half_batch" else 1)

    def step(state, u, eps):
        with jax.default_matmul_precision(precision):
            loss, g = jax.value_and_grad(job.nll)(state["params"], u[:rows],
                                                  eps[:rows])
            params, o = adamw_update(state["params"], g, state["opt"],
                                     opt["lr"], job.adamw_cfg)
        if fault == "unchanged":
            return state, loss
        return {"params": params, "opt": o}, loss

    job.step_fn = jax.jit(step)


# --------------------------------------------------------------------------
# the CPU rehearsal (tests/bench)
# --------------------------------------------------------------------------

# a 4-8-8-4 field on 8 samples, Pallas in interpret mode.  The limits are
# taken ten times wider: on this field the parameter change of the smallest
# leaves reads up to 1.24e-6 on the CPU, where the chip's limit, set at full
# size, is 1e-6; every fault still reads 1e-3 or more.
SMOKE = {
    "config": {"dim": 4, "hidden": [8, 8], "combine_backend": "pallas",
               "rtol": 1e-5, "atol": 1e-7},
    "traffic": {"batch": 8, "trace_steps": 2},
    "limits_scale": 10.0,
    # 8 lanes on 4 host devices, 2 a device: sharding alone moves the
    # program's readings and the reference's, each against itself on one
    # device, by up to 2e-5 (gradient and change, 8 seeds), where one
    # device reads 1e-6; every fault still reads 0.16 or more
    "per_traffic": {"train-b1000-lanes-symplectic": {"limits_scale": 100.0}},
}
