"""Plain float32 training reference, shared by the configurations' own
reference forwards (``configs/<config>.reference.py``).

It imports nothing of the system under test.  Every matrix product runs
through a :class:`Matmul`, which is float32 at ``highest`` precision for
the reference and, for the control that ``correct`` must reject, the same
product with both operands rounded to float8 (e4m3, one scale per
operand) — the next precision below the bfloat16 the configurations
state.  The rounding passes gradients straight through, so the control
differs from the reference only in the precision of its products.

Training follows the configuration's optimizer: gradients clipped by
their global norm, then AdamW with decoupled weight decay on float32
weights, from the weights of :mod:`generate` cast to float32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


@jax.custom_jvp
def _round_f8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = F8_MAX / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


@_round_f8.defjvp
def _round_f8_jvp(primals, tangents):
    return _round_f8(primals[0]), tangents[0]


class Matmul:
    """``mode`` "f32": float32 at highest precision; "fp8": the control."""

    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(mode)
        self.mode = mode

    def _q(self, x):
        x = x.astype(jnp.float32)
        return _round_f8(x) if self.mode == "fp8" else x

    def __call__(self, a, b):
        return jnp.matmul(self._q(a), self._q(b), precision=HIGHEST)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self._q(a), self._q(b), precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def cross_entropy(x, w_head, labels, mm: Matmul, mask=None, block=512):
    """Mean next-token cross-entropy of hidden states x [B, S, d] under the
    head [d, V], ``block`` rows at a time (the [rows, V] logits are
    recomputed in the backward pass, never kept whole)."""
    b, s, d = x.shape
    rows = b * s
    block = min(block, rows)
    xr = x.reshape(rows // block, block, d)
    lr = labels.reshape(rows // block, block)
    mr = (jnp.ones((rows,), jnp.float32) if mask is None
          else mask.reshape(rows).astype(jnp.float32)).reshape(
        rows // block, block)

    @jax.checkpoint
    def one(args):
        xb, lb, mb = args
        logits = mm(xb, w_head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - ll) * mb)

    total = jnp.sum(jax.lax.map(one, (xr, lr, mr)))
    return total / jnp.sum(mr)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                      for g in jax.tree.leaves(tree)])


def sample_indices(shapes, k: int = 16384, seed: int = 0):
    """Per leaf of ``shapes``, the flat positions at which the first
    gradient is compared element by element: every element of a leaf of at
    most ``k``, else ``k`` drawn (with replacement) from a fixed seed, the
    same for the program and the reference."""
    rng = np.random.default_rng(seed)
    out = []
    for leaf in jax.tree.leaves(shapes):
        n = math.prod(leaf.shape)
        out.append(np.arange(n) if n <= k else np.sort(rng.integers(0, n, k)))
    return [jnp.asarray(i, jnp.int32) for i in out]


def leaf_samples(tree, idx):
    """The elements of each leaf at ``idx`` (:func:`sample_indices`), as
    float32."""
    return [jnp.ravel(g)[i].astype(jnp.float32)
            for g, i in zip(jax.tree.leaves(tree), idx)]


def make_step(loss_fn, opt: dict, double_leaf: int = -1):
    """One clipped-AdamW step of ``loss_fn(params, tokens, labels)``; it
    also returns the clipped gradient's leaf norms and the new first
    moment's elements at ``idx`` (:func:`sample_indices`), which after the
    first step are (1 − β₁) times the gradient's.

    ``double_leaf`` ≥ 0 plants a fault for the calibration: that leaf's
    update is applied twice."""
    b1, b2 = opt["beta1"], opt["beta2"]
    lr, wd, eps, clip = (opt["learning_rate"], opt["weight_decay"],
                         opt["eps"], opt["grad_clip_norm"])

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, tokens, labels, idx):
        loss, g = jax.value_and_grad(loss_fn)(p, tokens, labels)
        gn = global_norm(g)
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
        g = jax.tree.map(lambda x: x * scale, g)
        m = jax.tree.map(lambda mi, gi: b1 * mi + (1 - b1) * gi, m, g)
        v = jax.tree.map(lambda vi, gi: b2 * vi + (1 - b2) * gi * gi, v, g)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t
        upd = jax.tree.map(
            lambda pi, mi, vi: lr * (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
            + lr * wd * pi, p, m, v)
        if double_leaf >= 0:
            leaves, tdef = jax.tree.flatten(upd)
            leaves[double_leaf] = 2.0 * leaves[double_leaf]
            upd = jax.tree.unflatten(tdef, leaves)
        p = jax.tree.map(lambda pi, ui: pi - ui, p, upd)
        return p, m, v, loss, leaf_norms(g), leaf_samples(m, idx)

    return step


def train_readings(loss_fn, params, batches, opt: dict, *,
                   double_leaf: int = -1):
    """Run len(batches) steps from ``params`` (float32, consumed).

    Returns (losses [k], first step's clipped-gradient leaf norms [L],
    its elements at :func:`sample_indices` per leaf, weights after the
    last step)."""
    step = make_step(loss_fn, opt, double_leaf)
    idx = sample_indices(params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1, s1 = [], None, None
    for t, (tokens, labels) in enumerate(batches, start=1):
        params, m, v, loss, gn, gs = step(params, m, v, jnp.float32(t),
                                          tokens, labels, idx)
        losses.append(loss)
        if g1 is None:
            g1 = gn
            s1 = [np.asarray(x, np.float64) / (1.0 - opt["beta1"])
                  for x in gs]
    del m, v
    return [float(x) for x in losses], g1, s1, params


def causal_attention(q, k, v, mm: Matmul, block: int = 512):
    """Softmax attention of q [B, S, H, D] over k, v [B, S, Kh, D] with a
    causal mask, grouped query heads sharing their key head; ``block``
    query rows at a time, each block recomputed in the backward pass."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    k = jnp.repeat(k, h // kh, axis=2)
    v = jnp.repeat(v, h // kh, axis=2)
    block = min(block, s)
    qb = q.reshape(b, s // block, block, h, d).transpose(1, 0, 2, 3, 4)
    starts = jnp.arange(s // block) * block

    @jax.checkpoint
    def one(args):
        qi, start = args
        sc = mm.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(d)
        rows = start + jnp.arange(block)[:, None]
        cols = jnp.arange(s)[None, :]
        sc = jnp.where((cols <= rows)[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return mm.einsum("bhqk,bkhd->bqhd", pr, v)

    out = jax.lax.map(one, (qb, starts))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def rope(x, theta: float):
    """Rotary embedding, half-split (rotate-half) layout, positions 0..S-1.
    x [B, S, H, D]."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
