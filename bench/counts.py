"""Operations and bytes of the work a kernel or a step must do, from the
logical shapes alone.

Nothing here counts tile padding or recomputation: a kernel that pads, or a
gradient strategy that replays the forward, does more than these counts,
and a roofline share or an MFU built on them can only read lower for it.
"""
from __future__ import annotations


def roofline(flops: float, bytes_moved: float, peak_flops: float,
             peak_bw: float) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    t_compute = flops / peak_flops
    t_memory = bytes_moved / peak_bw
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"


def causal_pairs(sq: int, sk: int, q_offset: int = 0) -> int:
    """(query, key) pairs a causal mask keeps: query i sees keys
    0..i + q_offset."""
    total = 0
    for i in range(sq):
        total += max(0, min(sk, i + q_offset + 1))
    return total


def attention_fwd_work(batch: int, heads: int, kv_heads: int, sq: int,
                       sk: int, head_dim: int, itemsize: int = 4,
                       causal: bool = True) -> tuple:
    """Forward attention: q k^T and p v over the pairs the mask keeps
    (2 * head_dim flops each); reads q, k, v once and writes the output.

    Returns (flops, bytes)."""
    pairs = causal_pairs(sq, sk) if causal else sq * sk
    flops = 4 * head_dim * pairs * batch * heads
    bytes_moved = (2 * batch * heads * sq * head_dim
                   + 2 * batch * kv_heads * sk * head_dim) * itemsize
    return flops, bytes_moved


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul per token: the attention and MLP
    projections of every layer and the output head (tied or not)."""
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_layer = (2 * d * h * hd            # q, o
                 + 2 * d * kv * hd         # k, v
                 + 3 * d * cfg["intermediate_size"])
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def lm_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Training FLOPs per token, PaLM's convention (Chowdhery et al. 2022,
    appendix B): 6 N + 12 L H Q T, with N the matmul parameters, L layers,
    H heads, Q the head size and T the sequence length.  Recomputation
    does not count."""
    return (6 * lm_matmul_params(cfg)
            + 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * seq_len)


def cnf_field_flops(dim: int, hidden, rows: int) -> int:
    """One evaluation of the CNF's augmented field on ``rows`` samples:
    the MLP's matmuls and the vector-Jacobian product of the Hutchinson
    estimate through them, 2 flops a multiply-add each way."""
    dims = [dim, *hidden, dim]
    macs = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return 4 * rows * macs
