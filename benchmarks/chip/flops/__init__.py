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
"""

from __future__ import annotations

import math
from typing import Tuple

import jax


def _pairs(s: int, causal: bool) -> float:
    return s * (s + 1) / 2.0 if causal else float(s) * s


def flash_attention(b: int, s: int, h: int, kh: int, d: int, *,
                    causal: bool = True, itemsize: int = 2
                    ) -> Tuple[float, float]:
    """Forward of GQA softmax attention: QKᵀ and PV, 2·d each per pair.
    Bytes: read q, k, v once and write o once."""
    flops = 4.0 * b * h * d * _pairs(s, causal)
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
    per_chunk = 3 * 2.0 * p * _pairs(c, causal) + 2 * 2.0 * c * p * p
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


def train_step(param_shapes, *, batch: int, seq: int, attn_layers: int = 0,
               heads: int = 0, head_dim: int = 0, mlstm_layers: int = 0,
               mlstm_heads: int = 0, mlstm_head_dim: int = 0,
               causal: bool = True) -> float:
    """Model FLOPs of one train step (forward and backward, 3× forward).

    Forward per token: 2 FLOPs per element of every weight of rank ≥ 2
    (each is used once per token, the sLSTM recurrent weight at its real
    block-diagonal shape), except the embedding table, which is a gather.
    On top: causal attention (4·d per visible pair and head) and the mLSTM
    recurrence in its recurrent form (C update and readout, 4·P² per token
    and head, plus 4·P for the normalizer)."""
    tokens = batch * seq
    dense = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(param_shapes)[0]:
        if len(leaf.shape) >= 2 and getattr(path[0], "key", None) != "embed":
            dense += 2.0 * math.prod(leaf.shape)
    fwd = dense * tokens
    fwd += attn_layers * 4.0 * batch * heads * head_dim * _pairs(seq, causal)
    fwd += mlstm_layers * tokens * mlstm_heads * (
        4.0 * mlstm_head_dim ** 2 + 4.0 * mlstm_head_dim)
    return 3.0 * fwd
