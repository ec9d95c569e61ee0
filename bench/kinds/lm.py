"""Decoder LMs trained in NODE mode: the layer stack as an euler depth solve
with a gradient strategy (``models/lm.py``), driven by the program's own
train step (``train/train_step.py``) as ``launch/train.py`` drives it.

The configuration file holds the published ``config.json`` keys of the
model (Qwen3 layout) plus ``node`` (solver), ``train`` (optimizer) and
``param_dtype``.  The traffic mix holds batch, sequence length, the
gradient strategy, the token chain and the limits of the comparison.

Weights are drawn by the benchmark from the seed in the published layout
(``hf_weights``) and handed to the program in its own layout
(``program_params``).  The program pairs the rotary dimensions (2i, 2i+1)
where Qwen3 pairs (i, i + head_dim/2); the two are the same model under a
fixed permutation of the q and k projections' head dimensions, which the
hand-over applies.

``reference_readings`` is the plain reference: the Qwen3 decoder in
``jax.numpy``, gradients by ``jax.grad`` with a checkpoint per layer, the
same clipping and AdamW, all written here and importing nothing from the
program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, counts, generate

# --------------------------------------------------------------------------
# weights in the published layout
# --------------------------------------------------------------------------

def _shapes(cfg: dict) -> dict:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    F = cfg["intermediate_size"]
    return {"in_norm": (L, d), "wq": (L, d, H * D), "wk": (L, d, KV * D),
            "wv": (L, d, KV * D), "wo": (L, H * D, d), "q_norm": (L, D),
            "k_norm": (L, D), "post_norm": (L, d), "wg": (L, d, F),
            "wu": (L, d, F), "wd": (L, F, d)}


def hf_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Matrices N(0, initializer_range^2), norm weights one, as the
    published model initializes; layers stacked on a leading axis."""
    std = cfg["initializer_range"]
    shapes = _shapes(cfg)
    keys = jax.random.split(key, len(shapes) + 1)
    layers = {}
    for k, (name, shape) in zip(keys[1:], shapes.items()):
        if name.endswith("norm"):
            layers[name] = jnp.ones(shape, dtype)
        else:
            layers[name] = (std * jax.random.normal(k, shape, jnp.float32)
                            ).astype(dtype)
    embed = (std * jax.random.normal(
        keys[0], (cfg["vocab_size"], cfg["hidden_size"]), jnp.float32)
             ).astype(dtype)
    return {"embed": embed, "final_norm": jnp.ones((cfg["hidden_size"],),
                                                   dtype),
            "layers": layers}


def rope_perm(head_dim: int) -> np.ndarray:
    """Program head-dim index j holds published index perm[j]."""
    half = head_dim // 2
    perm = np.empty(head_dim, np.int32)
    perm[0::2] = np.arange(half)
    perm[1::2] = np.arange(half) + half
    return perm


# --------------------------------------------------------------------------
# the program's side
# --------------------------------------------------------------------------

# program path inside a layer -> published name
_PROGRAM_LAYER = {("mixer_norm", "w"): "in_norm", ("attn", "wq"): "wq",
                  ("attn", "wk"): "wk", ("attn", "wv"): "wv",
                  ("attn", "wo"): "wo", ("attn", "q_norm", "w"): "q_norm",
                  ("attn", "k_norm", "w"): "k_norm",
                  ("ffn_norm", "w"): "post_norm", ("mlp", "wg"): "wg",
                  ("mlp", "wu"): "wu", ("mlp", "wd"): "wd"}


def program_arch(cfg: dict, gradient: str):
    from repro.configs.base import ArchConfig, LayerSpec, NodeConfig
    node = cfg["node"]
    return ArchConfig(
        name=cfg["name"], family="dense", d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        pattern=(LayerSpec("attn", "dense"),), qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        use_pallas=cfg["use_pallas"],
        node=NodeConfig(mode="node", method=node["method"],
                        n_steps=node["n_steps"], grad_mode=gradient,
                        combine_backend=node["combine_backend"]))


def program_params(hf: dict, cfg: dict) -> dict:
    """The published weights in the program's tree (``models/lm.py``)."""
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    perm = rope_perm(D)
    ly = hf["layers"]

    def heads(w, n):   # permute the head dim of a (L, d, n*D) projection
        L, d, _ = w.shape
        return w.reshape(L, d, n, D)[..., perm].reshape(L, d, n * D)

    unit = {"mixer_norm": {"w": ly["in_norm"]},
            "attn": {"wq": heads(ly["wq"], H), "wk": heads(ly["wk"], KV),
                     "wv": ly["wv"], "wo": ly["wo"],
                     "q_norm": {"w": ly["q_norm"][:, perm]},
                     "k_norm": {"w": ly["k_norm"][:, perm]}},
            "ffn_norm": {"w": ly["post_norm"]},
            "mlp": {"wg": ly["wg"], "wu": ly["wu"], "wd": ly["wd"]}}
    return {"embed": hf["embed"], "final_norm": {"w": hf["final_norm"]},
            "unit": (unit,)}


def _program_leaf_names(tree) -> list:
    """(published name, stacked?) of each leaf of a program param tree."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
        if keys[0] == "unit":
            out.append((_PROGRAM_LAYER[keys[2:]], True))
        else:
            out.append((keys[0], False))
    return out


def _norms(tree, stacked: str):
    """Per-leaf norms; per layer under the key ``stacked``."""
    def one(x, per_layer):
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if per_layer else None
        return jnp.sqrt(jnp.sum(x * x, axis=axes))
    return {k: jax.tree_util.tree_map(
        functools.partial(one, per_layer=k == stacked), v)
        for k, v in tree.items()}


def _name_values(names, leaves, n_layers) -> dict:
    out = {}
    for (name, stacked), v in zip(names, leaves):
        v = np.asarray(v, np.float64)
        if stacked:
            for l in range(n_layers):
                out[f"L{l:02d}.{name}"] = float(v[l])
        else:
            out[name] = float(v)
    return out


def _hf_name_values(tree, n_layers) -> dict:
    out = {"embed": float(tree["embed"]),
           "final_norm": float(tree["final_norm"])}
    for name, v in tree["layers"].items():
        v = np.asarray(v, np.float64)
        for l in range(n_layers):
            out[f"L{l:02d}.{name}"] = float(v[l])
    return out


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Qwen3's rotary embedding: dims (i, i + D/2) rotate together.
    x: (B, S, heads, D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _layer(cfg, x, p):
    B, S, d = x.shape
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    h = _rms(x, p["in_norm"], eps)
    q = (h @ p["wq"]).reshape(B, S, H, D)
    k = (h @ p["wk"]).reshape(B, S, KV, D)
    v = (h @ p["wv"]).reshape(B, S, KV, D)
    q = _rope(_rms(q, p["q_norm"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, p["k_norm"], eps), cfg["rope_theta"])
    q = q.reshape(B, S, KV, H // KV, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * D ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", a, v).reshape(B, S, H * D)
    x = x + o @ p["wo"]
    h = _rms(x, p["post_norm"], eps)
    return x + (jax.nn.silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]


def _nll_sum(cfg, params, tokens, labels, chunk):
    x = params["embed"][tokens]
    layer = jax.checkpoint(functools.partial(_layer, cfg))
    x, _ = jax.lax.scan(lambda c, p: (layer(c, p), None), x,
                        params["layers"])
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    B, S, d = x.shape
    xc = x.reshape(B, S // chunk, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(B, S // chunk, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(xi, li):
        logits = (xi @ params["embed"].T).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, li[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    return jnp.sum(jax.lax.map(lambda a: one(*a), (xc, lc)))


def _adamw(opt: dict, params, grads, m, v, t):
    b1, b2 = opt["b1"], opt["b2"]
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v,
                               grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m, v):
        p32 = p.astype(jnp.float32)
        new = p32 - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                 + opt["weight_decay"] * p32)
        return new.astype(p.dtype)

    return jax.tree_util.tree_map(upd, params, m, v), m, v, grads


def reference_readings(cfg: dict, traffic: dict, seed: int,
                       devices=None) -> dict:
    """Losses, first gradient and parameter change of the first
    ``compare_steps`` steps, in float32 at "highest", the gradient summed
    over blocks of rows so that it fits beside the optimizer state.  It
    runs on the first of ``devices`` (default: JAX's default device)."""
    rows, S = traffic["batch"], traffic["seq_len"]
    block = min(traffic["reference"]["block_rows"], rows)
    chunk = traffic["reference"]["loss_chunk"]
    opt = cfg["train"]
    n_layers = cfg["num_hidden_layers"]
    with jax.default_matmul_precision("highest"), \
            jax.default_device(devices[0] if devices else None):
        key = jnp.asarray(generate.seed_words(seed))
        make = jax.jit(lambda k: hf_weights(k, cfg))
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, t, l: _nll_sum(cfg, p, t, l, chunk)))
        acc_fn = jax.jit(lambda a, g, w: jax.tree_util.tree_map(
            lambda x, y: x + y.astype(jnp.float32) * w, a, g),
            donate_argnums=0)
        step_fn = jax.jit(functools.partial(_adamw, opt),
                          donate_argnums=(0, 1, 2, 3))
        norms = jax.jit(_norms, static_argnums=1)
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), p))
        params = make(key)
        m, v = zeros(params), zeros(params)
        out = {"loss": []}
        for t in range(traffic["compare_steps"]):
            b = generate.markov_tokens(seed, t, rows, S + 1,
                                       cfg["vocab_size"],
                                       traffic["token_noise"])
            total, grads = 0.0, zeros(params)
            for r in range(0, rows, block):
                nll, g = grad_fn(params, jnp.asarray(b["tokens"][r:r + block]),
                                 jnp.asarray(b["labels"][r:r + block]))
                total += float(nll)
                grads = acc_fn(grads, g, jnp.float32(1.0 / (rows * S)))
                del g
            out["loss"].append(total / (rows * S))
            params, m, v, clipped = step_fn(params, grads, m, v,
                                            jnp.float32(t + 1))
            if t == 0:
                out["grad"] = _hf_name_values(jax.device_get(
                    norms(clipped, "layers")), n_layers)
            del grads, clipped
        del m, v
        init = make(key)
        diff = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params, init)
        del params, init
        out["update"] = _hf_name_values(
            jax.device_get(norms(diff, "layers")), n_layers)
    return out


# --------------------------------------------------------------------------
# the job the harness drives
# --------------------------------------------------------------------------

class Job:
    def __init__(self, cell, devices, seed: int):
        from repro.optim import AdamWConfig, adamw_init, constant_schedule
        from repro.train import TrainConfig, make_grad_fn, make_train_step
        from repro.train.state import TrainState, init_solver_stats

        cfg, tr = cell.config, cell.traffic
        self.cfg, self.traffic, self.seed = cfg, tr, seed
        self.devices = devices
        self.warm_steps = tr["compare_steps"]
        self.trace_steps = tr["trace_steps"]
        self.limits = tr["limits"]
        opt = cfg["train"]
        self.arch = program_arch(cfg, tr["gradient"])
        self.tcfg = TrainConfig(
            lr=opt["lr"], max_grad_norm=opt["max_grad_norm"],
            adamw=AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                              weight_decay=opt["weight_decay"]),
            param_dtype=cfg["param_dtype"], loss_chunk=opt["loss_chunk"])
        self.key = jnp.asarray(generate.seed_words(seed))
        dtype = jnp.dtype(cfg["param_dtype"])

        def init(key):
            params = program_params(hf_weights(key, cfg, dtype), cfg)
            return TrainState(
                params=params, opt=adamw_init(params, self.tcfg.adamw),
                rng=jax.random.fold_in(key, 1),
                data_step=jnp.zeros((), jnp.int32),
                solver_stats=init_solver_stats(), compress_err=None)

        self.init = jax.jit(init)
        self.state = self.init(self.key)
        self.names = _program_leaf_names(self.state.params)
        self.step_fn = jax.jit(
            make_train_step(self.arch, self.tcfg,
                            lr_fn=constant_schedule(opt["lr"])),
            donate_argnums=0)
        self.grad_fn = make_grad_fn(self.arch, self.tcfg)
        self.readings = {"loss": []}
        self._batch = None

    # -- one step as launch/train.py drives it: host batch, jitted donated
    #    step, float(loss)
    def step(self, i: int) -> float:
        tr = self.traffic
        with jax.profiler.TraceAnnotation("bench.batch"):
            b = generate.markov_tokens(self.seed, i, tr["batch"],
                                       tr["seq_len"] + 1,
                                       self.cfg["vocab_size"],
                                       tr["token_noise"])
            batch = {k: jnp.asarray(v) for k, v in b.items()}
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.state, metrics = self.step_fn(self.state, batch)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            loss = float(metrics["loss"])
        self._batch = batch
        return loss

    def after_warm_step(self, i: int, loss: float) -> None:
        n = self.cfg["num_hidden_layers"]
        self.readings["loss"].append(loss)
        if i == 0:
            b1 = self.tcfg.adamw.b1
            g = jax.jit(lambda m: _norms(jax.tree_util.tree_map(
                lambda x: x / (1 - b1), m), "unit"))(self.state.opt["m"])
            self.readings["grad"] = _name_values(
                self.names, jax.tree_util.tree_leaves(jax.device_get(g)), n)
        if i == self.warm_steps - 1:
            dtype = jnp.dtype(self.cfg["param_dtype"])
            d = jax.jit(lambda p, k: _norms(jax.tree_util.tree_map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p, program_params(hf_weights(k, self.cfg, dtype),
                                  self.cfg)), "unit"))(self.state.params,
                                                       self.key)
            self.readings["update"] = _name_values(
                self.names, jax.tree_util.tree_leaves(jax.device_get(d)), n)

    # -- readings for the metrics
    def flops_per_step(self) -> float:
        tr = self.traffic
        return counts.lm_flops_per_token(self.cfg, tr["seq_len"]) \
            * tr["batch"] * tr["seq_len"]

    def attention_work(self) -> tuple:
        c, tr = self.cfg, self.traffic
        return counts.attention_fwd_work(
            tr["batch"], c["num_attention_heads"], c["num_key_value_heads"],
            tr["seq_len"], tr["seq_len"], c["head_dim"],
            jnp.dtype(c["param_dtype"]).itemsize)

    def grad_peak_bytes(self) -> int:
        """Compiler's peak of the gradient program alone."""
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (self.state.params, self._batch))
        exe = jax.jit(self.grad_fn).lower(*shapes).compile()
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

# the program's own path one precision below the configuration's float32:
# bfloat16 parameters (AdamW keeps float32 master copies)
CONTROL = {"param_dtype": "bfloat16"}
FAULTS = ("unchanged", "half_batch")


def faults(cell) -> tuple:
    """The faults ``plant`` can put into a job of ``cell``."""
    return FAULTS


def plant(job: Job, fault: str) -> None:
    """Break the job's timed step: ``unchanged`` returns the state as it
    came; ``half_batch`` drops the second half of every batch, so the mean
    is taken over the rest."""
    from repro.optim import constant_schedule
    from repro.train import make_train_step
    inner = make_train_step(job.arch, job.tcfg, lr_fn=constant_schedule(
        job.cfg["train"]["lr"]))
    half = job.traffic["batch"] // 2
    if fault == "unchanged":
        job.step_fn = jax.jit(lambda s, b: (s, inner(s, b)[1]))
    elif fault == "half_batch":
        job.step_fn = jax.jit(lambda s, b: inner(
            s, {k: v[:half] for k, v in b.items()}), donate_argnums=0)
    else:
        raise ValueError(fault)


# --------------------------------------------------------------------------
# the CPU rehearsal (tests/bench)
# --------------------------------------------------------------------------

# a 2-layer decoder of width 32 on 2 x 16 tokens, Pallas in interpret mode;
# the limits as the chip's
SMOKE = {
    "config": {"hidden_size": 32, "intermediate_size": 64,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 128,
               "use_pallas": True,
               "node": {"method": "euler", "n_steps": 2,
                        "combine_backend": "pallas"}},
    "traffic": {"batch": 2, "seq_len": 16, "trace_steps": 2,
                "reference": {"block_rows": 1, "loss_chunk": 8}},
    "limits_scale": 1.0,
}
