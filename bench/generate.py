"""The one generator of inputs: every batch and every weight comes from
``--seed`` and the traffic mix's parameters, and the same seed gives the
same inputs.  Copies of the repository's own generators
(``data/tokens.py::synthetic_lm_batch``, ``data/tabular.py``), kept here so
that no change to the program moves the yardstick.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """A raw JAX PRNG key (two uint32 words) holding all 64 bits of
    ``seed``; ``jax.random.PRNGKey`` would drop the high word."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], dtype=np.uint32)


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one stream (step, purpose) of one seed."""
    return np.random.default_rng([seed, *stream])


def markov_tokens(seed: int, step: int, batch: int, length: int, vocab: int,
                  noise: int) -> dict:
    """Rows of t_{i+1} = (a t_i + 7 + n_i) mod vocab with n_i uniform in
    [0, noise): a first-order chain whose next token has ``noise`` choices.
    Every row of every step differs.  Returns tokens and next-token labels,
    each (batch, length - 1) int32."""
    g = rng(seed, 1, step)
    first = g.integers(0, vocab, size=(batch, 1), dtype=np.int64)
    mult = 6364136223846793005 % vocab or 1
    steps = g.integers(0, noise, size=(batch, length - 1), dtype=np.int64)
    toks = np.empty((batch, length), np.int64)
    toks[:, :1] = first
    for i in range(length - 1):
        toks[:, i + 1] = (toks[:, i] * mult + 7 + steps[:, i]) % vocab
    toks = toks.astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def gaussian_mixture(seed: int, dim: int, components: int) -> dict:
    """A mixture in ``dim`` dimensions drawn from ``seed``: means
    N(0, 2^2), per-dim scales U(0.3, 0.8), equal weights; with its exact
    mean and standard deviation so that samples can be standardized."""
    g = rng(seed, 2)
    means = g.normal(0.0, 2.0, size=(components, dim))
    scales = g.uniform(0.3, 0.8, size=(components, dim))
    mean = means.mean(0)
    var = (scales ** 2 + means ** 2).mean(0) - mean ** 2
    return {"means": means, "scales": scales, "mean": mean,
            "std": np.sqrt(var)}


def mixture_batch(seed: int, step: int, mix: dict, batch: int) -> dict:
    """One standardized data batch and its Hutchinson noise, float32."""
    g = rng(seed, 3, step)
    comp = g.integers(0, mix["means"].shape[0], size=batch)
    x = mix["means"][comp] + g.normal(size=(batch, mix["means"].shape[1])) \
        * mix["scales"][comp]
    x = (x - mix["mean"]) / mix["std"]
    eps = g.normal(size=x.shape)
    return {"u": x.astype(np.float32), "eps": eps.astype(np.float32)}
