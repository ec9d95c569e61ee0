"""DeepSeek-V2 decoders trained in NODE mode, as one chip of an
expert-parallel deployment holds them: the dense first layers run
discretely, the expert layers are an euler depth solve with a gradient
strategy (``models/lm.py``), driven by the program's own train step
(``train/train_step.py``) as ``launch/train.py`` drives it.

The configuration file holds the published ``config.json`` keys, with the
layers, the routed experts and the vocabulary cut to this chip's share;
``deployment`` states the published counts and which experts are held
here.  The router keeps its published width and top-k over all experts;
this chip adds its held experts' part of each expert layer and the shared
experts, and what the absent experts would add is left out, in the program
and in the reference alike.

Weights are drawn by the benchmark from the seed in the published layout
(``hf_weights``: ``q_proj``, ``kv_a_proj_with_mqa`` = [latent | rope],
``kv_a_layernorm``, ``kv_b_proj`` = per head [nope | v], ``o_proj``, the
gate, the experts and the shared experts; each matrix as ``x @ W``) and
handed to the program in its own layout (``program_params``).  DeepSeek's
rope de-interleaves the rope dims and rotates halves, which rotates the
stored dims (2i, 2i+1) at frequency i, as the program does: no permutation.

``reference_readings`` is the plain reference: the DeepSeek-V2 decoder in
``jax.numpy`` at "highest", MLA and YaRN written out, the held experts
computed densely and masked by their gates, the sequence-level balance
loss, a checkpoint per layer, the chunked loss and the same clipping and
AdamW; it imports nothing from the program.
"""
from __future__ import annotations

import functools
import gc
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, generate
from bench.kinds.lm import FAULTS, _adamw, faults  # noqa: F401

# --------------------------------------------------------------------------
# sizes
# --------------------------------------------------------------------------


def _dims(cfg: dict) -> dict:
    dep = cfg["deployment"]
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "lora": cfg["kv_lora_rank"],
            "F": cfg["intermediate_size"], "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "E": dep["published_n_routed_experts"],
            "n": cfg["n_routed_experts"], "first": dep["held_experts_first"],
            "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "dense": cfg["first_k_dense_replace"],
            "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]}


def _attn_shapes(z: dict) -> dict:
    d, H = z["d"], z["H"]
    return {"input_layernorm": (d,),
            "q_proj": (d, H * (z["dn"] + z["dr"])),
            "kv_a_proj_with_mqa": (d, z["lora"] + z["dr"]),
            "kv_a_layernorm": (z["lora"],),
            "kv_b_proj": (z["lora"], H * (z["dn"] + z["dv"])),
            "o_proj": (H * z["dv"], d),
            "post_attention_layernorm": (d,)}


def _shapes(cfg: dict) -> dict:
    """{group: {name: shape}}; ``dense`` and ``moe`` stacked per layer."""
    z = _dims(cfg)
    d = z["d"]
    dense = {**_attn_shapes(z), "gate_proj": (d, z["F"]),
             "up_proj": (d, z["F"]), "down_proj": (z["F"], d)}
    moe = {**_attn_shapes(z), "gate": (d, z["E"]),
           "experts.gate_proj": (z["n"], d, z["f"]),
           "experts.up_proj": (z["n"], d, z["f"]),
           "experts.down_proj": (z["n"], z["f"], d),
           "shared_experts.gate_proj": (d, z["fs"]),
           "shared_experts.up_proj": (d, z["fs"]),
           "shared_experts.down_proj": (z["fs"], d)}
    return {"dense": {k: (z["dense"],) + s for k, s in dense.items()},
            "moe": {k: (z["moe"],) + s for k, s in moe.items()}}


# --------------------------------------------------------------------------
# weights in the published layout
# --------------------------------------------------------------------------

def hf_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """Matrices N(0, initializer_range^2), norm weights one.  One draw of
    normals is cut into the matrices, in the order of ``_shapes`` and then
    the embedding and the head."""
    std = cfg["initializer_range"]
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    shapes = _shapes(cfg)
    mats = [(g, name, shape) for g in ("dense", "moe")
            for name, shape in shapes[g].items()
            if not name.endswith("layernorm")]
    mats += [(None, "embed_tokens", (V, d)), (None, "lm_head", (d, V))]
    sizes = [math.prod(shape) for _, _, shape in mats]
    flat = std * jax.random.normal(key, (sum(sizes),), jnp.float32)
    out = {"dense": {}, "moe": {}, "norm": jnp.ones((d,), dtype)}
    for g in ("dense", "moe"):
        for name, shape in shapes[g].items():
            if name.endswith("layernorm"):
                out[g][name] = jnp.ones(shape, dtype)
    start = 0
    for (g, name, shape), size in zip(mats, sizes):
        w = flat[start:start + size].reshape(shape).astype(dtype)
        (out if g is None else out[g])[name] = w
        start += size
    return out


# --------------------------------------------------------------------------
# the program's side
# --------------------------------------------------------------------------

_ATTN = {("mixer_norm", "w"): "input_layernorm", ("attn", "wq"): "q_proj",
         ("attn", "wdkv"): "kv_a_proj_with_mqa.latent",
         ("attn", "wkr"): "kv_a_proj_with_mqa.rope",
         ("attn", "kv_norm", "w"): "kv_a_layernorm",
         ("attn", "wuk"): "kv_b_proj.nope", ("attn", "wuv"): "kv_b_proj.v",
         ("attn", "wo"): "o_proj",
         ("ffn_norm", "w"): "post_attention_layernorm"}
# program path inside a layer -> published name (split weights suffixed)
_PROGRAM_LAYER = {
    **_ATTN, ("mlp", "wg"): "gate_proj", ("mlp", "wu"): "up_proj",
    ("mlp", "wd"): "down_proj", ("moe", "router"): "gate",
    ("moe", "wg"): "experts.gate_proj", ("moe", "wu"): "experts.up_proj",
    ("moe", "wd"): "experts.down_proj",
    ("moe", "shared", "wg"): "shared_experts.gate_proj",
    ("moe", "shared", "wu"): "shared_experts.up_proj",
    ("moe", "shared", "wd"): "shared_experts.down_proj"}


def program_arch(cfg: dict, gradient: str):
    from repro.configs.base import ArchConfig, LayerSpec, NodeConfig
    from repro.nn.rope import YaRN
    z = _dims(cfg)
    rs = cfg["rope_scaling"]
    node = cfg["node"]
    # the program's router scales no gate: V2-Lite's published factor is 1
    if cfg["routed_scaling_factor"] != 1:
        raise ValueError("routed_scaling_factor "
                         f"{cfg['routed_scaling_factor']}: the program's "
                         "router has no scaling factor")
    return ArchConfig(
        name=cfg["name"], family="moe", d_model=z["d"],
        n_layers=cfg["num_hidden_layers"], n_heads=z["H"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=z["dn"],
        d_ff=z["F"], vocab=z["V"],
        prefix=(LayerSpec("mla", "dense"),) * z["dense"],
        pattern=(LayerSpec("mla", "moe"),),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YaRN(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        mla_kv_lora=z["lora"], mla_rope_dim=z["dr"], mla_nope_dim=z["dn"],
        mla_v_dim=z["dv"], moe_experts=z["E"], moe_top_k=z["k"],
        moe_d_ff=z["f"], moe_shared=cfg["n_shared_experts"],
        moe_shared_d_ff=z["fs"],
        moe_norm_topk_prob=cfg["norm_topk_prob"],
        moe_seq_aux=cfg["seq_aux"], moe_aux_alpha=cfg["aux_loss_alpha"],
        moe_held=(z["first"], z["n"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        use_pallas=cfg["use_pallas"],
        node=NodeConfig(mode="node", method=node["method"],
                        n_steps=node["n_steps"], grad_mode=gradient,
                        combine_backend=node["combine_backend"]))


def _program_layer(ly: dict, cfg: dict, moe: bool) -> dict:
    z = _dims(cfg)
    lora, H, dn, dv = z["lora"], z["H"], z["dn"], z["dv"]
    kva, kvb = ly["kv_a_proj_with_mqa"], ly["kv_b_proj"]
    kvb = kvb.reshape(kvb.shape[:-1] + (H, dn + dv))
    lead = kvb.shape[:-3]
    out = {"mixer_norm": {"w": ly["input_layernorm"]},
           "attn": {"wq": ly["q_proj"], "wdkv": kva[..., :lora],
                    "wkr": kva[..., lora:],
                    "kv_norm": {"w": ly["kv_a_layernorm"]},
                    "wuk": kvb[..., :dn].reshape(lead + (lora, H * dn)),
                    "wuv": kvb[..., dn:].reshape(lead + (lora, H * dv)),
                    "wo": ly["o_proj"]},
           "ffn_norm": {"w": ly["post_attention_layernorm"]}}
    if moe:
        out["moe"] = {
            "router": ly["gate"], "wg": ly["experts.gate_proj"],
            "wu": ly["experts.up_proj"], "wd": ly["experts.down_proj"],
            "shared": {"wg": ly["shared_experts.gate_proj"],
                       "wu": ly["shared_experts.up_proj"],
                       "wd": ly["shared_experts.down_proj"]}}
    else:
        out["mlp"] = {"wg": ly["gate_proj"], "wu": ly["up_proj"],
                      "wd": ly["down_proj"]}
    return out


def program_params(hf: dict, cfg: dict) -> dict:
    """The published weights in the program's tree (``models/lm.py``)."""
    out = {"embed": hf["embed_tokens"], "final_norm": {"w": hf["norm"]},
           "lm_head": hf["lm_head"],
           "unit": (_program_layer(hf["moe"], cfg, moe=True),)}
    for i in range(_dims(cfg)["dense"]):
        out[f"prefix_{i}"] = _program_layer(
            jax.tree_util.tree_map(lambda a: a[i], hf["dense"]), cfg,
            moe=False)
    return out


def _program_leaf_names(tree, n_dense: int) -> list:
    """(published name, first layer or None) of each leaf of a program
    param tree; the units' leaves are stacked over the expert layers."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
        if keys[0] == "unit":
            out.append((_PROGRAM_LAYER[keys[2:]], n_dense))
        elif keys[0].startswith("prefix_"):
            out.append((_PROGRAM_LAYER[keys[1:]], int(keys[0][7:])))
        else:
            out.append(({"embed": "embed_tokens", "final_norm": "norm"}.get(
                keys[0], keys[0]), None))
    return out


def _program_norms(tree):
    """Per-leaf norms, per layer for the stacked units."""
    def one(x, per_layer):
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if per_layer else None
        return jnp.sqrt(jnp.sum(x * x, axis=axes))
    return {k: jax.tree_util.tree_map(
        functools.partial(one, per_layer=k == "unit"), v)
        for k, v in tree.items()}


def _program_name_values(names, leaves) -> dict:
    out = {}
    for (name, first), v in zip(names, leaves):
        v = np.atleast_1d(np.asarray(v, np.float64))
        if first is None:
            out[name] = float(v[0])
        else:
            for j, x in enumerate(v):
                out[f"L{first + j:02d}.{name}"] = float(x)
    return out


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def _split_names(ly: dict, cfg: dict) -> dict:
    """The published layer's weights with the packed ones split as the
    program's tree splits them, for per-leaf readings."""
    z = _dims(cfg)
    lora, H, dn, dv = z["lora"], z["H"], z["dn"], z["dv"]
    out = {k: v for k, v in ly.items()
           if k not in ("kv_a_proj_with_mqa", "kv_b_proj")}
    kva, kvb = ly["kv_a_proj_with_mqa"], ly["kv_b_proj"]
    kvb = kvb.reshape(kvb.shape[:-1] + (H, dn + dv))
    out["kv_a_proj_with_mqa.latent"] = kva[..., :lora]
    out["kv_a_proj_with_mqa.rope"] = kva[..., lora:]
    out["kv_b_proj.nope"] = kvb[..., :dn]
    out["kv_b_proj.v"] = kvb[..., dn:]
    return out


def _hf_norms(tree, cfg: dict) -> dict:
    """Per-leaf norms of a published-layout tree, per layer in the
    stacked groups, with the packed weights split (``_split_names``)."""
    def norm(x, axes=None):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x, axis=axes))
    out = {k: norm(tree[k]) for k in ("embed_tokens", "norm", "lm_head")}
    for group in ("dense", "moe"):
        out[group] = {name: norm(v, tuple(range(1, v.ndim)))
                      for name, v in _split_names(tree[group], cfg).items()}
    return out


def _hf_name_values(norms: dict, cfg: dict) -> dict:
    """``_hf_norms`` on the host, named as the program's readings."""
    out = {k: float(norms[k]) for k in ("embed_tokens", "norm", "lm_head")}
    for group, first in (("dense", 0), ("moe", _dims(cfg)["dense"])):
        for name, v in norms[group].items():
            for j, x in enumerate(np.asarray(v, np.float64)):
                out[f"L{first + j:02d}.{name}"] = float(x)
    return out


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _yarn(cfg: dict):
    """(inverse frequencies, cos/sin factor, softmax scale) of DeepSeek's
    ``DeepseekV2YarnRotaryEmbedding`` and attention."""
    rs = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor = float(rs["factor"])

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / ((high - low) or 0.001),
                   0.0, 1.0)
    keep = 1.0 - ramp
    inv = inter * (1 - keep) + extra * keep
    amp = mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    if rs["mscale_all_dim"]:
        scale *= mscale(rs["mscale_all_dim"]) ** 2
    return inv, amp, scale


def _rope(x, inv, amp):
    """DeepSeek's ``apply_rotary_pos_emb``: de-interleave the rope dims,
    then rotate halves.  x: (B, S, heads, dr)."""
    S, dr = x.shape[1], x.shape[-1]
    ang = np.outer(np.arange(S), inv)
    emb = np.concatenate([ang, ang], -1)
    cos = jnp.asarray(np.cos(emb) * amp, jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(emb) * amp, jnp.float32)[None, :, None]
    x = x.astype(jnp.float32)
    x = x.reshape(x.shape[:-1] + (dr // 2, 2)).swapaxes(-1, -2).reshape(
        x.shape)
    rot = jnp.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], -1)
    return x * cos + rot * sin


def _attention(cfg, x, p):
    z = _dims(cfg)
    B, S, _ = x.shape
    H, dn, dr, dv, lora = z["H"], z["dn"], z["dr"], z["dv"], z["lora"]
    eps = cfg["rms_norm_eps"]
    inv, amp, scale = _yarn(cfg)
    q = (x @ p["q_proj"]).reshape(B, S, H, dn + dr)
    kva = x @ p["kv_a_proj_with_mqa"]
    kv = (_rms(kva[..., :lora], p["kv_a_layernorm"], eps) @ p["kv_b_proj"]
          ).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv, amp)], -1)
    k_pe = _rope(kva[..., None, lora:], inv, amp)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe, (B, S, H, dr))], -1)
    v = kv[..., dn:]
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def head(qkv):      # one head at a time: (B, S, S) scores
        qh, kh, vh = qkv
        s = jnp.einsum("bqd,bkd->bqk", qh, kh).astype(jnp.float32) * scale
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", a, vh)

    o = jax.lax.map(head, (q.transpose(2, 0, 1, 3), k.transpose(2, 0, 1, 3),
                           v.transpose(2, 0, 1, 3)))
    return o.transpose(1, 2, 0, 3).reshape(B, S, H * dv) @ p["o_proj"]


def _mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _experts(cfg, x, p):
    """(held experts' part + shared experts, the sequence balance loss)."""
    z = _dims(cfg)
    B, S, _ = x.shape
    E, k = z["E"], z["k"]
    logits = x.astype(jnp.float32) @ p["gate"].astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(scores, k)
    if cfg["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    else:
        gate = gate * cfg["routed_scaling_factor"]
    counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(1, 2))
    f = jax.lax.stop_gradient(counts * (E / (S * k)))
    aux = cfg["aux_loss_alpha"] * jnp.sum(f * jnp.mean(scores, axis=1), -1)
    y = _mlp(x, p["shared_experts.gate_proj"], p["shared_experts.up_proj"],
             p["shared_experts.down_proj"])
    for j in range(z["n"]):   # every token through each held expert
        w = jnp.sum(jnp.where(idx == z["first"] + j, gate, 0.0), -1)
        y = y + _mlp(x, p["experts.gate_proj"][j], p["experts.up_proj"][j],
                     p["experts.down_proj"][j]) * w[..., None].astype(x.dtype)
    return y, aux


def _layer(cfg, moe, carry, p):
    x, aux = carry
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, _rms(x, p["input_layernorm"], eps), p)
    h = _rms(x, p["post_attention_layernorm"], eps)
    if moe:
        y, a = _experts(cfg, h, p)
        aux = aux + a
    else:
        y = _mlp(h, p["gate_proj"], p["up_proj"], p["down_proj"])
    return x + y, aux


def _loss_parts(cfg, params, tokens, labels, chunk):
    """(summed next-token NLL, balance loss summed over rows and layers)."""
    x = params["embed_tokens"][tokens]
    carry = (x, jnp.zeros((x.shape[0],), jnp.float32))
    for moe, group in ((False, "dense"), (True, "moe")):
        layer = jax.checkpoint(functools.partial(_layer, cfg, moe))
        carry, _ = jax.lax.scan(lambda c, p: (layer(c, p), None), carry,
                                params[group])
    x, aux = carry
    x = _rms(x, params["norm"], cfg["rms_norm_eps"])
    B, S, d = x.shape
    xc = x.reshape(B, S // chunk, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(B, S // chunk, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(xi, li):
        logits = (xi @ params["lm_head"]).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, li[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    return jnp.sum(jax.lax.map(lambda a: one(*a), (xc, lc))), jnp.sum(aux)


def reference_readings(cfg: dict, traffic: dict, seed: int,
                       devices=None) -> dict:
    """Losses (next-token NLL plus the balance loss), first gradient and
    parameter change of the first ``compare_steps`` steps, in float32 at
    "highest".  The gradient runs on the first of ``devices`` (default:
    JAX's default device), summed over blocks of rows into one buffer; the
    clipping, AdamW and its moments run on the host's CPU device, so that
    the accelerator holds only the parameters and that sum."""
    rows, S = traffic["batch"], traffic["seq_len"]
    block = min(traffic["reference"]["block_rows"], rows)
    chunk = traffic["reference"]["loss_chunk"]
    opt = cfg["train"]
    dev = devices[0] if devices else jax.devices()[0]
    host = jax.devices("cpu")[0]

    def objective(p, t, l):
        nll, aux = _loss_parts(cfg, p, t, l, chunk)
        return nll / (rows * S) + aux / rows

    def add_grad(acc, p, t, l):
        val, g = jax.value_and_grad(objective)(p, t, l)
        return jax.tree_util.tree_map(jnp.add, acc, g), val

    def zeros(p):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), p)

    with jax.default_matmul_precision("highest"):
        key = jnp.asarray(generate.seed_words(seed))
        make = jax.jit(lambda k: hf_weights(k, cfg))
        grad_fn = jax.jit(add_grad, donate_argnums=0)
        step_fn = jax.jit(functools.partial(_adamw, opt),
                          donate_argnums=(0, 1, 2, 3))
        norms = jax.jit(lambda t: _hf_norms(t, cfg))
        with jax.default_device(dev):
            params_dev = make(key)
        params = jax.device_put(params_dev, host)
        with jax.default_device(host):
            m, v = jax.jit(zeros)(params), jax.jit(zeros)(params)
        out = {"loss": []}
        for t in range(traffic["compare_steps"]):
            b = generate.markov_tokens(seed, t, rows, S + 1,
                                       cfg["vocab_size"],
                                       traffic["token_noise"])
            with jax.default_device(dev):
                total, acc = 0.0, jax.jit(zeros)(params_dev)
                for r in range(0, rows, block):
                    acc, val = grad_fn(
                        acc, params_dev,
                        jnp.asarray(b["tokens"][r:r + block]),
                        jnp.asarray(b["labels"][r:r + block]))
                    total += float(val)
            out["loss"].append(total)
            grads = jax.device_put(acc, host)
            del acc, params_dev
            params, m, v, clipped = step_fn(params, grads, m, v,
                                            jnp.float32(t + 1))
            if t == 0:
                out["grad"] = _hf_name_values(
                    jax.device_get(norms(clipped)), cfg)
            del grads, clipped
            params_dev = jax.device_put(params, dev)
        del m, v, params_dev
        init = jax.device_put(make(key), host)
        diff = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))(params, init)
        del params, init
        out["update"] = _hf_name_values(jax.device_get(diff), cfg)
    return out


# --------------------------------------------------------------------------
# the job the harness drives
# --------------------------------------------------------------------------

def markov_tokens(seed: int, step: int, batch: int, length: int, vocab: int,
                  noise: int) -> dict:
    """``generate.markov_tokens``: the same draws and the same tokens, with
    the chain t_{i+1} = (a t_i + 7 + n_i) mod vocab composed as affine maps
    by doubling (log2(length) array passes) instead of a Python loop over
    the positions, which takes about 20 ms at 4 x 4097 while the device
    waits for the batch."""
    g = generate.rng(seed, 1, step)
    first = g.integers(0, vocab, size=(batch, 1), dtype=np.int64)
    mult = 6364136223846793005 % vocab or 1
    steps = g.integers(0, noise, size=(batch, length - 1), dtype=np.int64)
    # map i sends t_i to t_{i+1} = m t_i + b; after the doubling, map i
    # sends t_0 to t_{i+1}
    m = np.full(steps.shape, mult, np.int64)
    b = (steps + 7) % vocab
    shift = 1
    while shift < length - 1:
        m_prev, b_prev = m[:, :-shift], b[:, :-shift]
        b = np.concatenate([b[:, :shift],
                            (m[:, shift:] * b_prev + b[:, shift:]) % vocab], 1)
        m = np.concatenate([m[:, :shift], (m[:, shift:] * m_prev) % vocab], 1)
        shift *= 2
    toks = np.concatenate([first, (m * first + b) % vocab], 1)
    toks = toks.astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


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
        self.names = _program_leaf_names(self.state.params,
                                         cfg["first_k_dense_replace"])
        self.step_fn = jax.jit(
            make_train_step(self.arch, self.tcfg,
                            lr_fn=constant_schedule(opt["lr"])),
            donate_argnums=0)
        self.grad_fn = make_grad_fn(self.arch, self.tcfg)
        self.readings = {"loss": []}
        self._batch = None
        self._counts = None

    # -- one step as launch/train.py drives it: host batch, jitted donated
    #    step, float(loss)
    def step(self, i: int) -> float:
        tr = self.traffic
        with jax.profiler.TraceAnnotation("bench.batch"):
            b = markov_tokens(self.seed, i, tr["batch"], tr["seq_len"] + 1,
                              self.cfg["vocab_size"], tr["token_noise"])
            batch = {k: jnp.asarray(v) for k, v in b.items()}
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.state, metrics = self.step_fn(self.state, batch)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            loss = float(metrics["loss"])
        self._batch = batch
        return loss

    def after_warm_step(self, i: int, loss: float) -> None:
        self.readings["loss"].append(loss)
        if i == 0:
            b1 = self.tcfg.adamw.b1
            g = jax.jit(lambda m: _program_norms(jax.tree_util.tree_map(
                lambda x: x / (1 - b1), m)))(self.state.opt["m"])
            self.readings["grad"] = _program_name_values(
                self.names, jax.tree_util.tree_leaves(jax.device_get(g)))
        if i == self.warm_steps - 1:
            dtype = jnp.dtype(self.cfg["param_dtype"])
            d = jax.jit(lambda p, k: _program_norms(jax.tree_util.tree_map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p, program_params(hf_weights(k, self.cfg, dtype),
                                  self.cfg))))(self.state.params, self.key)
            self.readings["update"] = _program_name_values(
                self.names, jax.tree_util.tree_leaves(jax.device_get(d)))

    # -- readings for the metrics
    def flops_per_step(self) -> float:
        """PaLM's 6N + 12LHQT a token (recomputation does not count), with
        N the matmul parameters a token meets: the attention of every
        layer, the dense layers' MLP, the router and shared experts of each
        expert layer, and the held experts at the evaluations a token
        averages, k n / E; Q the mean of the qk and v head dims."""
        z, tr = _dims(self.cfg), self.traffic
        attn = sum(math.prod(s) for n, s in _attn_shapes(z).items()
                   if len(s) == 2)
        expert = 3 * z["d"] * z["f"]
        per_moe = (z["d"] * z["E"] + 3 * z["d"] * z["fs"]
                   + expert * z["k"] * z["n"] / z["E"])
        layers = z["dense"] + z["moe"]
        n = (layers * attn + z["dense"] * 3 * z["d"] * z["F"]
             + z["moe"] * per_moe + z["d"] * z["V"])
        q = (z["dn"] + z["dr"] + z["dv"]) / 2
        per_token = 6 * n + 12 * layers * z["H"] * q * tr["seq_len"]
        return per_token * tr["batch"] * tr["seq_len"]

    def attention_work(self) -> tuple:
        """Forward latent attention at qk head dim D = nope + rope and v
        head dim Dv: 2 D + 2 Dv flops a causal pair and head; reads q, k,
        v and writes the output once.  Returns (flops, bytes)."""
        z, tr = _dims(self.cfg), self.traffic
        B, S, H = tr["batch"], tr["seq_len"], z["H"]
        D, Dv = z["dn"] + z["dr"], z["dv"]
        item = jnp.dtype(self.cfg["param_dtype"]).itemsize
        pairs = S * (S + 1) // 2
        flops = (2 * D + 2 * Dv) * pairs * B * H
        bytes_moved = B * H * S * (2 * D + 2 * Dv) * item
        return flops, bytes_moved

    def grad_peak_bytes(self) -> int:
        """Compiler's peak of the gradient program alone."""
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (self.state.params, self._batch))
        exe = jax.jit(self.grad_fn).lower(*shapes).compile()
        return int(exe.memory_analysis().peak_memory_in_bytes)

    def expert_counts(self) -> np.ndarray:
        """(expert layers, held experts): the (token, expert) assignments
        each held expert got on the last batch, by the program's router on
        the program's depth states (the inputs of the expert layers)."""
        if self._counts is None:
            from repro.models import blocks as program_blocks
            from repro.models import lm as program_lm
            from repro.nn.moe import held_counts
            arch = self.arch
            R = arch.n_repeats
            spec = arch.pattern[0]

            def counts(params, tokens):
                x = program_lm.depth_input(params, arch, tokens)
                ys = program_lm.node_depth_states(
                    params, arch, x, jnp.arange(1, R + 1) / R)
                xs = jnp.concatenate([x[None], ys[:-1]])
                units = params["unit"][0]

                def one(_, xu):
                    route = program_blocks.layer_routing(xu[1], xu[0], spec,
                                                         arch)
                    return None, held_counts(route, arch.moe_config())

                return jax.lax.scan(one, None, (xs, units))[1]

            self._counts = np.asarray(jax.jit(counts)(
                self.state.params, self._batch["tokens"]))
        return self._counts

    def expert_load_imbalance(self) -> float:
        c = self.expert_counts().astype(np.float64)
        return float(np.mean(c.max(1) / c.mean(1)))

    def moe_experts_work(self) -> tuple:
        """(flops, bytes) of the held experts' grouped matmuls in one
        training step, from the counts: per expert layer, the three
        projections over the rows routed to held experts (2 d f flops a
        row each), the held experts' weights and the rows in and out; the
        forward twice (the solve and the symplectic replay), the backward
        once at twice the forward's flops and bytes."""
        z = _dims(self.cfg)
        item = jnp.dtype(self.cfg["param_dtype"]).itemsize
        rows = self.expert_counts().sum(1).astype(np.float64)
        flops = float(np.sum(3 * 2 * z["d"] * z["f"] * rows))
        weights = 3 * z["n"] * z["d"] * z["f"] * item * z["moe"]
        bytes_moved = weights + float(np.sum(2 * rows * z["d"] * item))
        return 4 * flops, 4 * bytes_moved

    def release(self) -> None:
        """Free the program's state and unload its executables: a loaded
        program keeps its reservation of device memory for temporaries
        (about 9 GB for this step), which the reference needs."""
        self.state = None
        self._batch = None
        self.step_fn = self.init = self.grad_fn = None
        jax.clear_caches()
        gc.collect()

    def check(self) -> dict:
        stats = self.devices[0].memory_stats() or {}
        print("memory before the reference", {
            k: stats.get(k) for k in ("bytes_in_use", "bytes_reserved",
                                      "largest_free_block_bytes")},
              file=sys.stderr)
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


def plant(job: Job, fault: str) -> None:
    """The LM's faults (``bench/kinds/lm.py: plant``), with the state
    donated in both: ``unchanged`` returns the state as it came, and
    ``half_batch`` drops the second half of every batch.  Without the
    donation the unchanged state and the step's temporaries do not fit
    one chip together."""
    from repro.optim import constant_schedule
    from repro.train import make_train_step
    inner = make_train_step(job.arch, job.tcfg, lr_fn=constant_schedule(
        job.cfg["train"]["lr"]))
    half = job.traffic["batch"] // 2
    if fault == "unchanged":
        job.step_fn = jax.jit(lambda s, b: (s, inner(s, b)[1]),
                              donate_argnums=0)
    elif fault == "half_batch":
        job.step_fn = jax.jit(lambda s, b: inner(
            s, {k: v[:half] for k, v in b.items()}), donate_argnums=0)
    else:
        raise ValueError(fault)


# --------------------------------------------------------------------------
# the CPU rehearsal (tests/bench)
# --------------------------------------------------------------------------

# 1 dense and 2 expert layers of width 32, 4 of 8 routed experts held, on
# 2 x 16 tokens, Pallas in interpret mode; the limits as the chip's
SMOKE = {
    "config": {"hidden_size": 32, "intermediate_size": 64,
               "moe_intermediate_size": 16, "num_hidden_layers": 3,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "kv_lora_rank": 16, "qk_nope_head_dim": 8,
               "qk_rope_head_dim": 8, "v_head_dim": 8,
               "n_routed_experts": 4, "num_experts_per_tok": 2,
               "vocab_size": 128, "use_pallas": True,
               "deployment": {"chips_per_layer": 2,
                              "published_n_routed_experts": 8,
                              "held_experts_first": 0,
                              "published_vocab_size": 256,
                              "published_num_hidden_layers": 3},
               "node": {"method": "euler", "n_steps": 2,
                        "combine_backend": "pallas"}},
    "traffic": {"batch": 2, "seq_len": 16, "trace_steps": 2,
                "reference": {"block_rows": 1, "loss_chunk": 8}},
    "limits_scale": 1.0,
}
