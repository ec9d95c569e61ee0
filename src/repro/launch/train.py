"""Distributed training driver with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --epochs 3 --steps-per-epoch 20 --ckpt-dir runs/ckpt \
        --ckpt-every 10 --metrics-out runs/metrics.jsonl [--resume]

The full train state — ``train.TrainState``: (params, AdamW state incl. the
LR-schedule step, RNG key, data cursor, solver stats, compression error
feedback) — is checkpointed as ONE pytree via ``runtime.Checkpointer`` with
async saves overlapping the train step.  On boot the driver restores from
the newest valid checkpoint if present (crash / preemption recovery);
``--resume`` makes that mandatory (exit 3 when no checkpoint exists).  The
data pipeline is keyed by step, so the token stream resumes exactly: the
fault-injection harness (tests/test_failures.py) SIGKILLs this driver
mid-epoch — including mid async save — and asserts the resumed
loss/grad-norm trajectory is BIT-identical to an uninterrupted run.

``--metrics-out`` appends one JSON line per step (flushed, so a killed run
leaves a complete prefix) — the harness and the CI train-smoke lane diff
these files across kill/resume boundaries (tools/check_resume_divergence.py).

Runs on whatever devices exist — a 1-CPU test, one TPU chip, a 256-chip
pod, or the 512-chip multi-pod mesh (``--mesh``), resharding the
checkpoint onto the current topology (elastic restart).

``main(argv)`` returns the run's record (per-step losses and seconds, the
jitted step, the final state and the last batch), so an in-process caller
such as ``chip_smoke.py`` can check the losses and lower the very step
function the loop ran.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=20,
                    help="total steps (ignored when --steps-per-epoch is "
                    "given)")
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--steps-per-epoch", type=int, default=None,
                    help="with --epochs: total = epochs * steps_per_epoch")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "constant"])
    ap.add_argument("--grad-mode", default=None,
                    help="node-mode gradient scheme (symplectic/...)")
    ap.add_argument("--node-method", default="euler")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true",
                    help="REQUIRE a valid checkpoint in --ckpt-dir and "
                    "boot from it (without this flag a present checkpoint "
                    "is still used, but an empty dir starts fresh)")
    ap.add_argument("--metrics-out", default=None,
                    help="append one JSON line per step (step/epoch/loss/"
                    "grad_norm/lr), flushed — for resume-divergence checks")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "pod", "multipod", "debug"])
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="inject a failure (fault-tolerance demo)")
    ap.add_argument("--step-delay-s", type=float, default=0.0,
                    help="sleep after each step — paces the loop so the "
                    "fault harness can SIGKILL mid-epoch deterministically")
    args = ap.parse_args(argv)

    import jax

    from repro.configs import get_arch, get_smoke_arch
    from repro.configs.base import NodeConfig
    from repro.data.tokens import TokenPipeline
    from repro.launch.mesh import make_debug_mesh, make_production_mesh
    from repro.optim import (CompressionConfig, cosine_schedule,
                             constant_schedule, wsd_schedule)
    from repro.parallel import make_sharder, state_specs
    from repro.runtime import Checkpointer, RetryConfig, mesh_shardings, \
        run_with_retries
    from repro.train import TrainConfig, init_train_state, make_train_step

    arch = get_smoke_arch(args.arch) if args.smoke else get_arch(args.arch)
    if args.grad_mode:
        arch = arch.with_(node=NodeConfig(mode="node",
                                          method=args.node_method,
                                          grad_mode=args.grad_mode))
    tcfg = TrainConfig(lr=args.lr, microbatches=args.microbatches,
                       compression=CompressionConfig(mode=args.compression))

    if args.steps_per_epoch is not None:
        total_steps = args.epochs * args.steps_per_epoch
        steps_per_epoch = args.steps_per_epoch
    else:
        total_steps = args.steps
        steps_per_epoch = max(1, (args.steps + args.epochs - 1)
                              // args.epochs)

    mesh = None
    if args.mesh == "pod":
        mesh = make_production_mesh(multi_pod=False)
    elif args.mesh == "multipod":
        mesh = make_production_mesh(multi_pod=True)
    elif args.mesh == "debug":
        mesh = make_debug_mesh()
    shard = make_sharder(mesh)

    sched = {"cosine": lambda: cosine_schedule(args.lr, 5, total_steps),
             "wsd": lambda: wsd_schedule(args.lr, 5,
                                         int(total_steps * 0.7),
                                         int(total_steps * 0.25)),
             "constant": lambda: constant_schedule(args.lr)}[args.schedule]()

    state = init_train_state(jax.random.PRNGKey(0), arch, tcfg)
    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = Checkpointer(args.ckpt_dir, keep=3, async_save=True)
        latest = ckpt.latest_step()
        if latest is None and args.resume:
            print(f"[train] --resume: no valid checkpoint in "
                  f"{args.ckpt_dir}", file=sys.stderr)
            sys.exit(3)
        if latest is not None:
            shardings = None
            if mesh is not None:
                shardings = mesh_shardings(mesh, state_specs(state, mesh))
            state, start_step = ckpt.restore(state, shardings=shardings)
            # the data cursor IS the checkpoint step: the pipeline resumes
            # the exact sample stream
            assert int(state["data_step"]) == start_step, \
                (int(state["data_step"]), start_step)
            print(f"[train] resumed from step {start_step} "
                  f"(epoch {start_step // steps_per_epoch})")
    elif args.resume:
        print("[train] --resume requires --ckpt-dir", file=sys.stderr)
        sys.exit(3)

    step_fn = make_train_step(arch, tcfg, lr_fn=sched, shard=shard)
    if mesh is not None:
        state_sh = mesh_shardings(mesh, state_specs(state, mesh))
        step_fn = jax.jit(step_fn, in_shardings=(state_sh, None),
                          out_shardings=(state_sh, None),
                          donate_argnums=(0,))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    pipe = iter(TokenPipeline(args.global_batch, args.seq_len, arch.vocab,
                              start_step=start_step))
    metrics_f = None
    if args.metrics_out:
        out_dir = os.path.dirname(args.metrics_out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        metrics_f = open(args.metrics_out, "a")

    t0 = time.time()
    epoch_losses = []
    losses, step_seconds, batch = [], [], None
    for step in range(start_step, total_steps):
        batch = next(pipe)
        if arch.encdec:
            batch["frames"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.global_batch, args.seq_len,
                                           arch.d_frontend))
        if arch.frontend == "patch":
            batch["patch_embeds"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.global_batch, 4,
                                           arch.d_frontend))

        def do_step():
            if step == args.fail_at_step:
                args.fail_at_step = -1   # fail once
                raise RuntimeError("injected failure (demo)")
            return step_fn(state, batch)

        def on_failure():
            print(f"[train] step {step} failed; state intact, retrying")

        t_step = time.perf_counter()
        state, metrics = run_with_retries(do_step, RetryConfig(),
                                          on_failure)
        epoch = step // steps_per_epoch
        loss = float(metrics["loss"])          # waits for the step
        step_seconds.append(time.perf_counter() - t_step)
        losses.append(loss)
        epoch_losses.append(loss)
        if metrics_f is not None:
            # json round-trips python floats exactly (repr-based), so the
            # resume-divergence check compares bit-identical values
            metrics_f.write(json.dumps(
                {"step": step, "epoch": epoch, "loss": loss,
                 "grad_norm": float(metrics["grad_norm"]),
                 "lr": float(metrics["lr"])}) + "\n")
            metrics_f.flush()
        if step % 5 == 0 or step == total_steps - 1:
            print(f"[train] step {step:5d} loss {loss:.4f}"
                  f" gnorm {float(metrics['grad_norm']):.3f}"
                  f" lr {float(metrics['lr']):.2e}"
                  f" {time.time() - t0:.1f}s")
        if (step + 1) % steps_per_epoch == 0:
            print(f"[train] epoch {epoch} done: mean loss "
                  f"{sum(epoch_losses) / len(epoch_losses):.4f} "
                  f"({len(epoch_losses)} steps)")
            epoch_losses = []
        if ckpt is not None and (step + 1) % args.ckpt_every == 0:
            # async: the host transfer is the only stall; the file write
            # overlaps the next step (bench_checkpoint measures both)
            ckpt.save(step + 1, state, block=False)
        if args.step_delay_s:
            time.sleep(args.step_delay_s)
    if ckpt is not None:
        ckpt.save(total_steps, state)
        ckpt.wait()
    if metrics_f is not None:
        metrics_f.close()
    sstats = jax.tree_util.tree_map(int, state["solver_stats"])
    print(f"[train] done (solver stats {sstats})")
    return {"losses": losses, "step_seconds": step_seconds,
            "step_fn": step_fn, "state": state, "batch": batch}


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
