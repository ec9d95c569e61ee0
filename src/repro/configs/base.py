"""Architecture / run configuration dataclasses.

An ArchConfig is a complete, declarative description of one model: the layer
pattern (a repeating unit scanned over depth + optional prefix layers), the
mixer/FFN hyperparameters, and the training-mode knobs (node_mode = the
paper's neural-ODE depth formulation + gradient scheme).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.nn.attention import AttnConfig
from repro.nn.mamba import MambaConfig
from repro.nn.moe import MoEConfig
from repro.nn.rope import YaRN
from repro.nn.xlstm import XLSTMConfig


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str   # "attn" | "mla" | "mamba" | "mlstm" | "slstm"
    ffn: str     # "dense" | "moe" | "none"


@dataclasses.dataclass(frozen=True)
class NodeConfig:
    """The paper's technique as a first-class training mode.

    mode:
      "off"    — standard discrete residual stack.
      "node"   — depth-time neural ODE over the layer stack:
                 f(x, t) = unit_{floor(t*R)}(x), integrated with ``method``
                 over [0,1] with n_steps (= R by default).  With
                 method="euler" the forward map is IDENTICAL to the discrete
                 stack, so grad_mode="symplectic" gives exact gradients with
                 O(R + s + one-unit) live memory.
    grad_mode: a gradient strategy for ``repro.core.solve`` — either a
      registered name (symplectic | backprop | remat_step | remat_solve |
      adjoint) or a ``GradientStrategy`` instance carrying its own knobs
      (e.g. ``ContinuousAdjoint(steps_multiplier=4)``); resolved via
      ``repro.core.as_gradient`` at the solve call (core/api.py).
    combine_backend: auto | jnp | pallas — how RK stage combinations over the
      stacked slope buffers execute (auto = Pallas kernel on TPU, jnp oracle
      elsewhere; see core/combine.py).
    """
    mode: str = "off"
    method: str = "euler"
    n_steps: int = 0               # 0 => one step per repeat unit
    grad_mode: object = "symplectic"
    combine_backend: str = "auto"


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    pattern: Tuple[LayerSpec, ...]
    prefix: Tuple[LayerSpec, ...] = ()
    # attention
    qk_norm: bool = False
    window: Optional[int] = None
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0
    rope_scaling: Optional[YaRN] = None   # read by MLA
    mla_kv_lora: int = 0           # >0 enables MLA fields
    mla_rope_dim: int = 64
    mla_nope_dim: int = 128
    mla_v_dim: int = 128
    # moe
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    moe_shared: int = 0
    moe_shared_d_ff: int = 0
    moe_norm_topk_prob: bool = True
    moe_seq_aux: bool = False
    moe_aux_alpha: float = 0.01
    # (first, n): the routed experts this chip holds of each expert layer;
    # None computes all of them under the capacity-factor dispatch
    moe_held: Optional[Tuple[int, int]] = None
    # ssm
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    xlstm_heads: int = 4
    # enc-dec / frontends
    encdec: bool = False
    enc_layers: int = 0
    frontend: str = "none"         # none | audio | patch
    d_frontend: int = 0
    # misc
    norm_eps: float = 1e-6
    residual_scale: float = 1.0    # minicpm depth-scaled residuals
    tie_embeddings: bool = False
    # training mode
    node: NodeConfig = NodeConfig()
    remat: bool = True             # checkpoint each scanned unit
    scan_unit: bool = True         # lax.scan over repeat units
    use_pallas: Optional[bool] = None

    @property
    def n_repeats(self) -> int:
        body = self.n_layers - len(self.prefix)
        assert body % len(self.pattern) == 0, \
            (self.name, body, len(self.pattern))
        return body // len(self.pattern)

    def attn_config(self) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            qk_norm=self.qk_norm, window=self.window,
            rope_theta=self.rope_theta, rotary_pct=self.rotary_pct,
            mla=self.mla_kv_lora > 0, kv_lora=self.mla_kv_lora or 512,
            rope_head_dim=self.mla_rope_dim, nope_head_dim=self.mla_nope_dim,
            v_head_dim=self.mla_v_dim, rope_scaling=self.rope_scaling)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            d_model=self.d_model, d_ff=self.moe_d_ff or self.d_ff,
            n_experts=self.moe_experts, top_k=self.moe_top_k,
            n_shared=self.moe_shared, shared_d_ff=self.moe_shared_d_ff,
            router_aux_weight=self.moe_aux_alpha,
            norm_topk_prob=self.moe_norm_topk_prob,
            seq_aux=self.moe_seq_aux, held=self.moe_held)

    def mamba_config(self) -> MambaConfig:
        return MambaConfig(d_model=self.d_model,
                           d_state=self.mamba_d_state,
                           d_conv=self.mamba_d_conv,
                           expand=self.mamba_expand)

    def xlstm_config(self) -> XLSTMConfig:
        return XLSTMConfig(d_model=self.d_model, n_heads=self.xlstm_heads)

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str          # train_4k | prefill_32k | decode_32k | long_500k
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}
