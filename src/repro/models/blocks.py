"""Per-layer block dispatch: init / forward / cache-init for every mixer."""
from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.nn.attention import (gqa_attention, init_gqa, init_gqa_cache,
                                init_mla, init_mla_cache, mla_attention)
from repro.nn.common import no_shard, split_keys
from repro.nn.mamba import init_mamba, init_mamba_state, mamba_forward
from repro.nn.mlp import init_swiglu, swiglu
from repro.nn.moe import Routing, init_moe, moe_ffn, moe_route
from repro.nn.norm import init_rmsnorm, rmsnorm
from repro.nn.xlstm import (init_mlstm, init_mlstm_state, init_slstm,
                            init_slstm_state, mlstm_forward, slstm_forward)


def init_layer(key, spec: LayerSpec, cfg: ArchConfig, dtype=jnp.float32):
    ks = split_keys(key, 4)
    p: dict = {"mixer_norm": init_rmsnorm(cfg.d_model, dtype)}
    if spec.mixer == "attn":
        p["attn"] = init_gqa(ks[0], cfg.attn_config(), dtype)
    elif spec.mixer == "mla":
        p["attn"] = init_mla(ks[0], cfg.attn_config(), dtype)
    elif spec.mixer == "mamba":
        p["mamba"] = init_mamba(ks[0], cfg.mamba_config(), dtype)
    elif spec.mixer == "mlstm":
        p["mlstm"] = init_mlstm(ks[0], cfg.xlstm_config(), dtype)
    elif spec.mixer == "slstm":
        p["slstm"] = init_slstm(ks[0], cfg.xlstm_config(), dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        p["ffn_norm"] = init_rmsnorm(cfg.d_model, dtype)
        if spec.ffn == "dense":
            p["mlp"] = init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dtype)
        elif spec.ffn == "moe":
            p["moe"] = init_moe(ks[1], cfg.moe_config(), dtype)
        else:
            raise ValueError(spec.ffn)
    return p


def init_layer_cache(spec: LayerSpec, cfg: ArchConfig, batch: int,
                     max_len: int, dtype=jnp.bfloat16):
    if spec.mixer == "attn":
        return init_gqa_cache(cfg.attn_config(), batch, max_len, dtype)
    if spec.mixer == "mla":
        return init_mla_cache(cfg.attn_config(), batch, max_len, dtype)
    if spec.mixer == "mamba":
        return init_mamba_state(cfg.mamba_config(), batch)
    if spec.mixer == "mlstm":
        return init_mlstm_state(cfg.xlstm_config(), batch)
    if spec.mixer == "slstm":
        return init_slstm_state(cfg.xlstm_config(), batch)
    raise ValueError(spec.mixer)


def _mixer_residual(p, x, spec: LayerSpec, cfg: ArchConfig, *, cache, pos,
                    positions, shard, causal):
    """x + mixer(norm(x)): (x, new_cache)."""
    up = cfg.use_pallas
    h = rmsnorm(p["mixer_norm"], x, eps=cfg.norm_eps, use_pallas=up)
    if spec.mixer == "attn":
        y, new_cache = gqa_attention(p["attn"], h, cfg.attn_config(),
                                     positions=positions, cache=cache,
                                     pos=pos, shard=shard, use_pallas=up,
                                     causal=causal)
    elif spec.mixer == "mla":
        y, new_cache = mla_attention(p["attn"], h, cfg.attn_config(),
                                     positions=positions, cache=cache,
                                     pos=pos, shard=shard, use_pallas=up)
    elif spec.mixer == "mamba":
        y, new_cache = mamba_forward(p["mamba"], h, cfg.mamba_config(),
                                     state=cache, shard=shard)
    elif spec.mixer == "mlstm":
        y, new_cache = mlstm_forward(p["mlstm"], h, cfg.xlstm_config(),
                                     state=cache, shard=shard)
    elif spec.mixer == "slstm":
        y, new_cache = slstm_forward(p["slstm"], h, cfg.xlstm_config(),
                                     state=cache, shard=shard)
    else:
        raise ValueError(spec.mixer)
    return x + cfg.residual_scale * y, new_cache


def layer_forward(p, x, spec: LayerSpec, cfg: ArchConfig, *,
                  cache: Optional[Any] = None, pos=None, positions=None,
                  shard=no_shard, causal: bool = True):
    """Pre-norm residual block: x + mixer(norm(x)) [+ ffn(norm(x))].

    Returns (x, new_cache, aux_loss)."""
    x, new_cache = _mixer_residual(p, x, spec, cfg, cache=cache, pos=pos,
                                   positions=positions, shard=shard,
                                   causal=causal)
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn != "none":
        h = rmsnorm(p["ffn_norm"], x, eps=cfg.norm_eps,
                    use_pallas=cfg.use_pallas)
        if spec.ffn == "dense":
            y = swiglu(p["mlp"], h, shard=shard)
        else:
            y, aux = moe_ffn(p["moe"], h, cfg.moe_config(), shard=shard)
        x = x + cfg.residual_scale * y
    return x, new_cache, aux


def layer_routing(p, x, spec: LayerSpec, cfg: ArchConfig) -> Routing:
    """The routing of an expert layer for its input x (training shapes):
    what ``moe_route`` decides inside ``layer_forward``."""
    x, _ = _mixer_residual(p, x, spec, cfg, cache=None, pos=None,
                           positions=None, shard=no_shard, causal=True)
    h = rmsnorm(p["ffn_norm"], x, eps=cfg.norm_eps, use_pallas=cfg.use_pallas)
    return moe_route(p["moe"], h, cfg.moe_config())
