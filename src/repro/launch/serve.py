"""Serving drivers: LM token decoding and the continuous-batching ODE engine.

    # batched LM serving: prefill a request batch, then decode tokens
    PYTHONPATH=src python -m repro.launch.serve lm --arch qwen3-0.6b --smoke \
        --batch 4 --prompt-len 32 --gen-len 16

    # ODE solve serving: heterogeneous request stream through repro.serve
    PYTHONPATH=src python -m repro.launch.serve ode --smoke

The bare legacy form (no subcommand) still routes to ``lm``:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke

``main(["ode", ...])`` returns the run's record (the engine, the requests
and their results, and the field that defines them), so an in-process
caller such as ``chip_smoke.py`` can check every result.
"""
from __future__ import annotations

import argparse
import sys
import time


def _lm_main(argv=None):
    ap = argparse.ArgumentParser(prog="repro.launch.serve lm")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve the params from a TRAINING checkpoint "
                    "(the full TrainState saved by repro.launch.train; "
                    "pass the same --grad-mode/--node-method the training "
                    "run used so the param pytree structures match)")
    ap.add_argument("--grad-mode", default=None)
    ap.add_argument("--node-method", default="euler")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch, get_smoke_arch
    from repro.configs.base import NodeConfig
    from repro.data.tokens import synthetic_lm_batch
    from repro.train import (TrainConfig, init_train_state,
                             make_decode_step, make_prefill_step)

    arch = get_smoke_arch(args.arch) if args.smoke else get_arch(args.arch)
    if args.grad_mode:
        arch = arch.with_(node=NodeConfig(mode="node",
                                          method=args.node_method,
                                          grad_mode=args.grad_mode))
    state = init_train_state(jax.random.PRNGKey(0), arch, TrainConfig())
    if args.ckpt_dir:
        # train -> serve handoff: the fresh state is only the restore
        # template (same arch => same pytree structure), every param is
        # overwritten with the trained values
        from repro.runtime import Checkpointer
        state, ck_step = Checkpointer(args.ckpt_dir).restore(state)
        print(f"[serve] restored params from {args.ckpt_dir} "
              f"step {ck_step}")
    params = state["params"]

    max_len = args.prompt_len + args.gen_len
    prefill = jax.jit(make_prefill_step(arch, args.batch, max_len))
    decode = jax.jit(make_decode_step(arch))

    b = synthetic_lm_batch(0, args.batch, args.prompt_len + 1, arch.vocab)
    batch = {"tokens": jnp.asarray(b["tokens"])}
    if arch.encdec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len,
                                    arch.d_frontend))
    if arch.frontend == "patch":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, 4, arch.d_frontend))

    t0 = time.time()
    logits, caches = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    offset = 4 if arch.frontend == "patch" else 0
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    key = jax.random.PRNGKey(0)
    t0 = time.time()
    for i in range(args.gen_len - 1):
        pos = jnp.int32(args.prompt_len + offset + i)
        logits, caches = decode(params, caches, tok, pos)
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits[:, -1] / args.temperature)[:, None]
            tok = tok.astype(jnp.int32)
        else:
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"[serve] arch={arch.name} batch={args.batch} "
          f"prefill {args.prompt_len} tok in {t_prefill*1e3:.1f} ms; "
          f"decode {args.gen_len} tok in {t_decode*1e3:.1f} ms "
          f"({t_decode/max(args.gen_len-1,1)*1e3:.1f} ms/tok)")
    print("[serve] sample generation (token ids):", gen[0][:16].tolist())


def _ode_main(argv=None):
    ap = argparse.ArgumentParser(prog="repro.launch.serve ode")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: rot-check that the engine runs")
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--method", default="dopri5")
    ap.add_argument("--buckets", type=int, nargs="+", default=[4, 8, 16])
    ap.add_argument("--max-steps", type=int, default=512)
    ap.add_argument("--rate", type=float, default=None,
                    help="offered load in requests/s (Poisson arrivals); "
                    "default: submit everything up front and drain")
    ap.add_argument("--naive", action="store_true",
                    help="also run the sequential single-solve baseline")
    args = ap.parse_args(argv)
    if args.smoke:
        args.dim, args.hidden = 4, 8
        args.requests = min(args.requests, 8)
        args.buckets = [2, 4]

    import jax
    import jax.numpy as jnp

    from repro.core import AdaptiveConfig
    from repro.core.tableau import get_tableau
    from repro.serve import (EngineConfig, SolveEngine, latency_summary,
                             naive_sequential_solve, poisson_arrivals,
                             serve_timed, synthetic_stream)

    dim, hidden = args.dim, args.hidden
    k = jax.random.split(jax.random.PRNGKey(args.seed + 17), 4)
    params = {"w1": jax.random.normal(k[0], (dim, hidden)) * 0.4,
              "b1": jax.random.normal(k[1], (hidden,)) * 0.1,
              "w2": jax.random.normal(k[2], (hidden, dim)) * 0.4,
              "b2": jax.random.normal(k[3], (dim,)) * 0.1}

    # The stream asks for rtol down to 1e-6.  A TPU's default f32 matmul
    # rounds its operands to bf16, and that noise in f puts a floor under
    # the step's error estimate: a third of the requests then never meet
    # their tolerance.  "highest" keeps the field in f32 on every backend.
    def field(x, t, p):
        h = jnp.tanh(jnp.dot(x, p["w1"], precision="highest") + p["b1"])
        return jnp.dot(h, p["w2"], precision="highest") + p["b2"]

    cfg = AdaptiveConfig(rtol=1e-4, atol=1e-6, max_steps=args.max_steps,
                         initial_step=0.02)
    reqs = synthetic_stream(args.requests, dim, seed=args.seed)

    t0 = time.perf_counter()
    engine = SolveEngine(field, get_tableau(args.method), cfg, params,
                         x0_template=jnp.zeros((dim,)),
                         engine_cfg=EngineConfig(buckets=tuple(args.buckets)))
    t_init = time.perf_counter() - t0
    print(f"[serve ode] engine up in {t_init:.2f}s "
          f"(AOT advance for buckets {tuple(args.buckets)})")

    arrivals = None
    if args.rate is not None:
        arrivals = poisson_arrivals(args.requests, args.rate, seed=args.seed)
    t0 = time.perf_counter()
    results = serve_timed(engine, reqs, arrivals)
    wall = time.perf_counter() - t0
    ok = sum(r.succeeded for r in results.values())
    lat = latency_summary(results)
    print(f"[serve ode] {len(results)} requests ({ok} ok) in {wall:.2f}s "
          f"-> {len(results)/wall:.1f} req/s"
          + (f" at offered {args.rate:.1f} req/s" if args.rate else
             " (drain mode)"))
    print(f"[serve ode] latency p50 {lat['p50_ms']:.1f} ms, "
          f"p99 {lat['p99_ms']:.1f} ms; engine stats {engine.stats}")

    if args.naive:
        _, lats = naive_sequential_solve(field, get_tableau(args.method),
                                         cfg, params, reqs)
        import numpy as np
        wall_n = float(np.sum(lats))       # steady state: warmup excluded
        print(f"[serve ode] naive sequential: {len(reqs)} requests in "
              f"{wall_n:.2f}s -> {len(reqs)/wall_n:.1f} req/s; per-solve "
              f"p50 {np.percentile(lats, 50)*1e3:.1f} ms")
    return {"engine": engine, "requests": reqs, "results": results,
            "field": field, "params": params, "cfg": cfg,
            "tableau": get_tableau(args.method)}


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("lm", "ode"):
        return {"lm": _lm_main, "ode": _ode_main}[argv[0]](argv[1:])
    # legacy spelling: no subcommand = the original LM driver flags
    return _lm_main(argv)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
