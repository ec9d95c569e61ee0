"""Prove that the main path of this repository runs on a TPU.

    python chip_smoke.py                # one chip: device, train, exact, serve
    python chip_smoke.py --four-chips   # four chips: the sharded lane solve

Everything runs in this one process, through the entry points a user calls
(``repro.launch.train.main`` and ``repro.launch.serve.main``):

* device -- fail unless JAX's first device is a TPU; print its kind, the
  device count and the JAX and libtpu versions.
* train -- three NODE-mode training steps of qwen3-0.6b at its published
  widths and all 28 layers (euler depth solve, symplectic-adjoint
  gradients, AdamW), batch 8 x 512 tokens.  Every loss must be finite, and
  the compiled step must contain the Pallas kernels (``tpu_custom_call``).
* exact -- the same architecture cut to 2 layers: the parameter gradient
  of the symplectic adjoint against backpropagation through the solver
  (the paper's exactness claim), at "highest" matmul precision.
* serve -- ``launch.serve ode`` at its defaults; every request must
  succeed, the engine's answers must be as accurate against an f64
  reference as the sequential solve's, and the engine's AOT ``advance``
  must contain the Pallas kernels.
* --four-chips -- only the mesh-sharded masked-batch solve (adaptive
  dopri5, symplectic adjoint, with its gradient) against the same solve on
  one device.

Any failure raises and the exit code is non-zero.  On success the last line
of standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

KERNEL_MARK = "tpu_custom_call"

TRAIN_ARGV = ["--arch", "qwen3-0.6b", "--grad-mode", "symplectic",
              "--node-method", "euler", "--steps", "3", "--global-batch",
              "8", "--seq-len", "512"]

# exact phase: the train arch cut in depth only, one batch of the train shape
EXACT_ARCH = "qwen3-0.6b"
EXACT_SMOKE = False
EXACT_LAYERS = 2
EXACT_BATCH = 8
EXACT_SEQ = 512
# Relative L2 gap between the symplectic and the backprop parameter
# gradient.  The same phase on the CPU (XLA CPU, f32, batch 8 x 512, jnp
# reference kernels) measured 1.601481102090376e-06; the bound is ten times
# that.  The TPU's default f32 matmul precision is not f32 (it rounds
# operands to bf16), so the phase runs under
# jax.default_matmul_precision("highest"); at the default precision the two
# gradients would differ by bf16 rounding, which says nothing about the
# adjoint.
EXACT_CPU_GAP = 1.601481102090376e-06
EXACT_BOUND = 10 * EXACT_CPU_GAP

SERVE_ARGV = ["ode"]
# serve phase: every request's error is measured against an f64 host solve
# (scipy DOP853, rtol 1e-12) in the engine's own norm, the RMS of
# err / (atol + rtol |x|).  The engine and the sequential solve integrate
# in f32; rounding flips some accept/reject decisions between the lane-
# batched and the one-request program, and then the two answers differ by
# their global errors, which local error control does not bound (the CPU
# already shows 30x the tolerance between them on one request of this
# stream).  So the check is that the engine is as accurate as solving
# each request alone: its median and its largest error are at most this
# factor times the sequential solve's.
SERVE_ACCURACY_FACTOR = 2.0
SERVE_REF_RTOL, SERVE_REF_ATOL = 1e-12, 1e-14

# --four-chips: lanes x state dim of the sharded solve
SHARD_LANES = 64
SHARD_DIM = 32
SHARD_DEVICES = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(jax) -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's first device is {dev.platform!r} ({dev}); this "
            "script proves the program on the chip and has no CPU mode")


def assert_kernels(text: str, what: str) -> None:
    if KERNEL_MARK not in text:
        raise RuntimeError(f"{what}: no {KERNEL_MARK} in the compiled "
                           "program, so no Pallas kernel ran")
    log(f"[{what}] {text.count(KERNEL_MARK)} {KERNEL_MARK} ops in the "
        "compiled program")


def peak_bytes(jax) -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def rel_l2(a, b) -> float:
    import jax
    import jax.numpy as jnp
    num = sum(float(jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32))
                            ** 2))
              for x, y in zip(jax.tree_util.tree_leaves(a),
                              jax.tree_util.tree_leaves(b)))
    den = sum(float(jnp.sum(y.astype(jnp.float32) ** 2))
              for y in jax.tree_util.tree_leaves(b))
    return math.sqrt(num / den)


def phase_device(jax) -> None:
    require_tpu(jax)
    dev = jax.devices()[0]
    log(f"[device] {dev.device_kind} x{len(jax.devices())} "
        f"(platform {dev.platform}); jax {jax.__version__}, "
        f"libtpu {importlib.metadata.version('libtpu')}")


def phase_train(jax) -> None:
    import numpy as np

    from repro.configs import get_arch
    from repro.launch import train

    arch = get_arch(TRAIN_ARGV[TRAIN_ARGV.index("--arch") + 1])
    log(f"[train] {arch.name}: {arch.n_layers} layers, d_model "
        f"{arch.d_model}, vocab {arch.vocab}; argv {' '.join(TRAIN_ARGV)}")
    rec = train.main(TRAIN_ARGV)
    losses, secs = rec["losses"], rec["step_seconds"]
    for i, (loss, s) in enumerate(zip(losses, secs)):
        log(f"[train] step {i}: loss {loss!r}, {s!r} s"
            + (" (includes compile)" if i == 0 else ""))
    if len(losses) != int(TRAIN_ARGV[TRAIN_ARGV.index("--steps") + 1]):
        raise RuntimeError(f"train: {len(losses)} losses")
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"train: non-finite loss in {losses}")
    log(f"[train] peak_bytes_in_use {peak_bytes(jax)}")
    t0 = time.perf_counter()
    compiled = rec["step_fn"].lower(rec["state"], rec["batch"]).compile()
    log(f"[train] lowered the step again in {time.perf_counter() - t0!r} s")
    assert_kernels(compiled.as_text(), "train")


def phase_exact(jax) -> None:
    import jax.numpy as jnp

    from repro.configs import get_arch, get_smoke_arch
    from repro.configs.base import NodeConfig
    from repro.data.tokens import TokenPipeline
    from repro.train import TrainConfig, init_train_state, make_grad_fn

    base = (get_smoke_arch if EXACT_SMOKE else get_arch)(EXACT_ARCH)
    base = base.with_(n_layers=EXACT_LAYERS)
    tcfg = TrainConfig()
    params = init_train_state(jax.random.PRNGKey(0), base, tcfg).params
    batch = {k: jnp.asarray(v) for k, v in next(iter(TokenPipeline(
        EXACT_BATCH, EXACT_SEQ, base.vocab))).items()}
    grads = {}
    with jax.default_matmul_precision("highest"):
        for mode in ("symplectic", "backprop"):
            arch = base.with_(node=NodeConfig(mode="node", method="euler",
                                              grad_mode=mode))
            g, loss, _ = jax.jit(make_grad_fn(arch, tcfg))(params, batch)
            grads[mode] = g
            log(f"[exact] {mode}: loss {float(loss)!r}")
    gap = rel_l2(grads["symplectic"], grads["backprop"])
    log(f"[exact] {base.name} cut to {EXACT_LAYERS} layers, batch "
        f"{EXACT_BATCH}x{EXACT_SEQ}: relative L2 gap symplectic vs backprop "
        f"{gap!r} (bound {EXACT_BOUND!r})")
    if not gap <= EXACT_BOUND:
        raise RuntimeError(f"exact: gap {gap!r} above {EXACT_BOUND!r}")


def phase_serve(jax) -> None:
    import numpy as np

    from repro.launch import serve
    from repro.serve import naive_sequential_solve

    rec = serve.main(SERVE_ARGV)
    reqs, results = rec["requests"], rec["results"]
    ok = sum(r.succeeded for r in results.values())
    if len(results) != len(reqs) or ok != len(reqs):
        failed = [(rid, reqs[rid].rtol, r.n_accepted, r.n_attempts)
                  for rid, r in sorted(results.items()) if not r.succeeded]
        raise RuntimeError(f"serve: {ok} of {len(reqs)} requests succeeded "
                           f"({len(results)} results); failed (id, rtol, "
                           f"accepted, attempts): {failed}")
    naive, _ = naive_sequential_solve(rec["field"], rec["tableau"],
                                      rec["cfg"], rec["params"], reqs,
                                      warmup=False)
    from scipy.integrate import solve_ivp
    p = {k: np.asarray(v, np.float64) for k, v in rec["params"].items()}

    def field_f64(t, x):   # launch.serve ode's field, in numpy f64
        return np.tanh(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    err_eng, err_seq, steps_differ = [], [], 0
    for rid, (req, sol) in enumerate(zip(reqs, naive)):
        ref = solve_ivp(field_f64, (req.t0, req.t1),
                        np.asarray(req.x0, np.float64), method="DOP853",
                        rtol=SERVE_REF_RTOL, atol=SERVE_REF_ATOL).y[:, -1]
        scale = req.atol + req.rtol * np.abs(ref)
        for errs, x in ((err_eng, results[rid].x_final),
                        (err_seq, sol.x_final)):
            errs.append(float(np.sqrt(np.mean(
                ((np.asarray(x, np.float64) - ref) / scale) ** 2))))
        steps_differ += results[rid].n_accepted != int(sol.n_accepted)
    stats = {name: (float(np.median(e)), max(e))
             for name, e in (("engine", err_eng), ("sequential", err_seq))}
    log(f"[serve] {ok}/{len(reqs)} requests succeeded; {steps_differ} took "
        f"another number of steps than the sequential solve; error against "
        f"the f64 reference in units of the request tolerance (median, "
        f"max): engine {stats['engine']!r}, sequential "
        f"{stats['sequential']!r}")
    (med_e, max_e), (med_s, max_s) = stats["engine"], stats["sequential"]
    if not (med_e <= SERVE_ACCURACY_FACTOR * med_s
            and max_e <= SERVE_ACCURACY_FACTOR * max_s):
        raise RuntimeError(f"serve: the engine is less accurate than solving "
                           f"each request alone: {stats}")
    for lanes, exe in sorted(rec["engine"].compiled_advance.items()):
        assert_kernels(exe.as_text(), f"serve advance B={lanes}")


def phase_four_chips(jax) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import AdaptiveConfig, SymplecticAdjoint, solve
    from repro.launch.mesh import make_lane_mesh

    mesh = make_lane_mesh((SHARD_DEVICES,))
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = 2 * SHARD_DIM
    params = {"w1": jax.random.normal(k[0], (SHARD_DIM, hidden)) * 0.3,
              "w2": jax.random.normal(k[1], (hidden, SHARD_DIM)) * 0.3}
    # heterogeneous magnitudes -> heterogeneous per-lane step counts
    x0 = jax.random.normal(k[2], (SHARD_LANES, SHARD_DIM)) * jnp.linspace(
        0.5, 3.0, SHARD_LANES)[:, None]
    cfg = AdaptiveConfig(rtol=1e-5, atol=1e-7, max_steps=256)

    def field(x, t, p):
        return jnp.tanh(x @ p["w1"] + t) @ p["w2"]

    def run(m):
        def loss(p):
            sol = solve(field, x0, p, method="dopri5",
                        gradient=SymplecticAdjoint(), stepping=cfg,
                        batch_axis=0, mesh=m)
            return jnp.sum(sol.ys ** 2), sol
        (_, sol), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(
            params)
        return jax.block_until_ready((sol, g))

    with jax.default_matmul_precision("highest"):
        sol1, g1 = run(None)
        sol4, g4 = run(mesh)
    for name, leaf in [("ys", sol4.ys)] + [
            (f"grad {n}", v) for n, v in sorted(g4.items())]:
        n_dev = len(leaf.sharding.device_set)
        if n_dev != SHARD_DEVICES:
            raise RuntimeError(f"four-chips: {name} lives on {n_dev} "
                               f"devices, not {SHARD_DEVICES}")
    if not (bool(jnp.all(sol1.success)) and bool(jnp.all(sol4.success))):
        raise RuntimeError("four-chips: a lane failed to reach t1")
    steps_differ = int(np.sum(np.asarray(sol1.stats["n_steps"])
                              != np.asarray(sol4.stats["n_steps"])))
    y1, y4 = np.asarray(sol1.ys), np.asarray(sol4.ys)
    ys_scaled = float(np.max(np.abs(y4 - y1)
                             / (cfg.atol + cfg.rtol * np.abs(y1))))
    g_gap = rel_l2(g4, g1)
    log(f"[four-chips] {SHARD_LANES} lanes on mesh {dict(mesh.shape)}: ys "
        f"sharded over {len(sol4.ys.sharding.device_set)} devices; lanes "
        f"whose step count differs {steps_differ}; ys difference "
        f"{ys_scaled!r} x (atol + rtol |y|); gradient relative L2 gap "
        f"{g_gap!r}")
    if not (ys_scaled <= 1.0 and g_gap <= 1e-4):
        raise RuntimeError("four-chips: sharded and one-device solves "
                           "disagree")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded lane solve on 4 chips")
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    log(f"[setup] compile cache {enable_compile_cache()}")
    import jax

    phase_device(jax)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_train, phase_exact, phase_serve])
    for phase in phases:
        t0 = time.perf_counter()
        phase(jax)
        log(f"[{phase.__name__}] done in {time.perf_counter() - t0!r} s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
