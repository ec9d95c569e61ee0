"""Losses: causal-LM cross entropy (f32 accumulation, ignore_index)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.runtime import scopes

IGNORE = -100


def lm_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """logits (B,S,V) f32; labels (B,S) int32 (IGNORE masked)."""
    with jax.named_scope(scopes.LM_LOSS):
        mask = (labels != IGNORE)
        safe = jnp.where(mask, labels, 0)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                   safe[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


def lm_loss_chunked(hidden: jnp.ndarray, head: jnp.ndarray,
                    labels: jnp.ndarray, chunk: int = 512) -> jnp.ndarray:
    """Cross entropy computed per sequence chunk: the (B, chunk, V) logits
    block is materialized, reduced, and rematerialized in backward — the
    full (B, S, V) float32 logits tensor (the dominant live buffer of
    big-vocab training) never exists.

    hidden: (B, S, d) final normed hidden states; head: (d, V).
    """
    with jax.named_scope(scopes.LM_LOSS):
        B, S, d = hidden.shape
        chunk = min(chunk, S)
        pad = (-S) % chunk
        if pad:
            hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)),
                             constant_values=IGNORE)
            S += pad
        nb = S // chunk
        hc = hidden.reshape(B, nb, chunk, d).transpose(1, 0, 2, 3)
        lc = labels.reshape(B, nb, chunk).transpose(1, 0, 2)

        @jax.checkpoint
        def one(xi, li):
            logits = (xi @ head).astype(jnp.float32)
            mask = (li != IGNORE)
            safe = jnp.where(mask, li, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, safe[..., None],
                                       axis=-1)[..., 0]
            return jnp.sum((logz - gold) * mask), jnp.sum(mask)

        def body(carry, xs):
            s, c = carry
            ds, dc = one(*xs)
            return (s + ds, c + dc.astype(jnp.int32)), None

        (nll, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.int32(0)),
                                     (hc, lc))
        return nll / jnp.maximum(cnt, 1)
