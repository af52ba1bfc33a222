"""Operations and bytes that a kernel call or a train step needs, from shapes.

Every count is what the algorithm needs, not what an implementation
happens to execute: causal attention counts the S(S+1)/2 visible pairs,
the chunkwise mLSTM counts the causal half of each chunk's [C, C] tiles,
and recomputation (remat, the kernels' jnp backward) counts nothing.  A
roofline share built on these counts therefore cannot pass 100% unless
the kernel time leaves out part of the work.

``causal=False`` gives the full-tile count, which is what XLA's HLO of
the jnp twins executes; the tests compare that form with
``repro.launch.roofline``'s HLO count.

What belongs to one kernel or one layer kind sits in a file of its own,
found by name:

* ``kernels/<tag>.py``, one per kernel with a roofline share: ``MATCH``,
  the substrings of a ``tpu_custom_call`` HLO line that give the call
  the tag ``<tag>`` (the file's name), and ``call(cfg, rc, traffic)``,
  the operations and bytes of one call in the cell's step, or ``None``
  where the step does not run the kernel;
* ``layers/<kind>.py``, one per layer kind of a pattern:
  ``sequence_flops(cfg, spec, batch, seq, causal)``, the forward FLOPs of
  one such layer beyond its weights (:func:`train_step`).
"""

from __future__ import annotations

import functools
import math
import types
from pathlib import Path
from typing import Tuple

import jax

import harness

HERE = Path(__file__).resolve().parent


@functools.cache
def kernel_modules() -> types.MappingProxyType:
    """Tag -> module of every file under ``kernels/``."""
    return types.MappingProxyType(
        {p.stem: harness.load_module(p, f"bench_flops_kernel_{p.stem}")
         for p in sorted((HERE / "kernels").glob("*.py"))})


@functools.cache
def layer_module(kind: str):
    """The module of layer kind ``kind`` (``layers/<kind>.py``)."""
    path = HERE / "layers" / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"layer kind {kind!r} has no flops/layers/{kind}.py:"
                         f" its model FLOPs would go uncounted")
    return harness.load_module(path, f"bench_flops_layer_{kind}")


def pairs(s: int, causal: bool, window: int = None) -> float:
    """Query-key pairs of one head: the visible ones under a causal mask,
    where a sliding window of ``window`` lets a query see at most that
    many keys, itself included."""
    if causal and window is not None and window < s:
        return window * (window + 1) / 2.0 + float(s - window) * window
    return s * (s + 1) / 2.0 if causal else float(s) * s


def flash_attention(b: int, s: int, h: int, kh: int, d: int, *,
                    causal: bool = True, window: int = None,
                    itemsize: int = 2) -> Tuple[float, float]:
    """Forward of GQA softmax attention: QKᵀ and PV, 2·d each per visible
    pair.  Bytes: read q, k, v once and write o once."""
    flops = 4.0 * b * h * d * pairs(s, causal, window)
    nbytes = (2.0 * b * s * h * d + 2.0 * b * s * kh * d) * itemsize
    return flops, nbytes


def mlstm_chunk(b: int, s: int, h: int, p: int, chunk: int, *,
                causal: bool = True, itemsize: int = 2
                ) -> Tuple[float, float]:
    """Forward of the chunkwise mLSTM: per chunk of C rows, the intra-chunk
    q·kᵀ, (q·kᵀ∘w)·v and w·k products over the visible pairs (2·P each),
    and the inter-chunk q·C and kᵀ·v products (2·C·P² each).  Bytes: read
    q, k, v and the two f32 gate columns, write h."""
    c = min(chunk, s)
    n_chunks = s // c
    per_chunk = 3 * 2.0 * p * pairs(c, causal) + 2 * 2.0 * c * p * p
    flops = b * h * n_chunks * per_chunk
    nbytes = 4.0 * b * s * h * p * itemsize + 2.0 * b * s * h * 4
    return flops, nbytes


def gp_gram(n: int, m: int, d: int, passes: int = 6
            ) -> Tuple[float, float]:
    """Matérn-5/2 (cross-)Gram of n×d against m×d points: the rank-d
    distance product, 2·n·m·d, in bf16 passes of the MXU — the kernel's
    float32 product runs at ``highest`` precision, six passes, which is
    the least the chip can do at the precision it states.  Bytes: read
    both point sets, write the f32 [n, m] result."""
    return 2.0 * n * m * d * passes, 4.0 * (n * d + m * d + n * m)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def _is_expert(path) -> bool:
    """A routed expert's weight: a leaf under a block's ``moe/experts/``."""
    keys = [getattr(k, "key", None) for k in path]
    return any(a == "moe" and b == "experts" for a, b in zip(keys, keys[1:]))


def train_step(param_shapes, cfg, *, batch: int, seq: int,
               experts_published: int = None, causal: bool = True) -> float:
    """Model FLOPs of one train step (forward and backward, 3× forward) of
    the model ``cfg`` whose parameters have the shapes ``param_shapes``.

    Forward per token: 2 FLOPs per element of every weight of rank ≥ 2
    (each is used once per token, the sLSTM recurrent weight at its real
    block-diagonal shape), except the embedding table, which is a gather.
    A routed expert's weight counts at k / E_published of that, k the
    experts per token and E_published ``experts_published`` (by default
    the experts held): of the E experts held here, a token uses
    k · E / E_published.  The router and shared experts count in full.
    A tied head adds its product, 2·V·d per token.  On top, each layer
    of the pattern adds its kind's sequence term (``layers/<kind>.py``):
    causal attention's pairs, the mLSTM and SSD recurrences.

    These are what the algorithm needs: a dense ``moe_impl`` that runs
    every expert on every token executes more, and the count does not
    follow it."""
    terms = [(spec, layer_module(spec.kind)) for spec in cfg.pattern]
    tokens = batch * seq
    dense = routed = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(param_shapes)[0]:
        if len(leaf.shape) < 2 or getattr(path[0], "key", None) == "embed":
            continue
        if _is_expert(path):
            routed += 2.0 * math.prod(leaf.shape)
        else:
            dense += 2.0 * math.prod(leaf.shape)
    fwd = dense * tokens
    if routed:
        share = cfg.n_experts_per_tok / (experts_published or cfg.n_experts)
        fwd += routed * share * tokens
    if cfg.tie_embeddings:
        fwd += 2.0 * cfg.vocab_size * cfg.d_model * tokens
    for spec, mod in terms:
        fwd += cfg.n_groups * mod.sequence_flops(cfg, spec, batch, seq,
                                                 causal)
    return 3.0 * fwd
