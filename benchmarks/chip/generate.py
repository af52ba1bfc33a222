"""Weights and token batches made from ``--seed`` on the device.

Both the timed program and the plain reference take their weights from
here, so the reference needs nothing that the program made.

Weights: every leaf of the model's parameter tree (its shapes from
``jax.eval_shape`` of the model's own init) is drawn from its own key,
``fold_in(key, leaf index)``: norm scales are ones, biases zeros, the
embedding table N(0, 1/d) and every other matrix a truncated normal of
standard deviation 1/sqrt(fan-in), fan-in being the second-to-last axis
(the sLSTM's block-diagonal recurrent weight counts its block size).

Tokens: a copy of the synthetic stream of ``repro.train.data`` — a
Zipf(1.1) unigram distribution over the vocabulary with a BOS token (0)
every ``doc_len`` positions — drawn by inverse CDF, so a pool of batches
is one small program and never a [batch, seq, vocab] array.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp


def leaf_specs(shapes) -> List[Tuple[str, tuple, str]]:
    """(path, shape, dtype name) of every leaf, in ``jax.tree`` order."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out.append((name, tuple(leaf.shape), jnp.dtype(leaf.dtype).name))
    return out


def _rule(path: str, shape: tuple) -> Tuple[str, float]:
    last = path.rsplit("/", 1)[-1]
    if last == "scale":
        return "ones", 0.0
    if last in ("b", "bias") or len(shape) < 2:
        return "zeros", 0.0
    if path.startswith("embed/"):
        return "normal", 1.0 / math.sqrt(shape[-1])
    return "truncated", 1.0 / math.sqrt(shape[-2])


def init_params(key, shapes):
    """The whole tree, as the model's init lays it out, drawn in float32 on
    the device in one jitted call.

    These float32 draws are the optimizer's master weights and the
    reference's weights; the program's parameters are their rounding to
    the leaves' own dtype, made by the caller in a program of its own
    (inside the drawing program XLA may skip a rounding that a cast back
    to float32 follows)."""
    specs = tuple((p, s) for p, s, _ in leaf_specs(shapes))
    return _init_params(key, specs=specs, treedef=jax.tree.structure(shapes))


@partial(jax.jit, static_argnames=("specs", "treedef"))
def _init_params(key, *, specs, treedef):
    leaves = []
    for i, (path, shape) in enumerate(specs):
        kind, std = _rule(path, shape)
        k = jax.random.fold_in(key, i)
        if kind == "ones":
            x = jnp.ones(shape, jnp.float32)
        elif kind == "zeros":
            x = jnp.zeros(shape, jnp.float32)
        elif kind == "normal":
            x = jax.random.normal(k, shape, jnp.float32) * std
        else:
            x = jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                            jnp.float32) * std
        leaves.append(x)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@partial(jax.jit, static_argnames=("n", "batch", "seq", "vocab", "zipf",
                                   "doc_len"))
def token_pool(key, *, n: int, batch: int, seq: int, vocab: int,
               zipf: float, doc_len: int):
    """``n`` batches of {tokens, labels} [n, batch, seq] (int32)."""
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    p = jnp.exp(-zipf * jnp.log(ranks))
    cdf = jnp.cumsum(p) / jnp.sum(p)
    u = jax.random.uniform(key, (n, batch, seq + 1), jnp.float32)
    toks = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
    pos = jnp.arange(seq + 1)
    toks = jnp.where((pos % doc_len == 0)[None, None, :], 0, toks)
    toks = toks.astype(jnp.int32)
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
