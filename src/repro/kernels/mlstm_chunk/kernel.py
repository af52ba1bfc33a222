"""Pallas TPU chunkwise-parallel mLSTM forward.

The mLSTM (xLSTM's matrix-memory cell) is a gated linear-attention
recurrence.  The TPU-native formulation splits the sequence into chunks:
*within* a chunk everything is dense MXU work ([C,C] and [C,P] matmuls);
*across* chunks only the (P×P) matrix memory, the (P,) normalizer and a
scalar stabilizer are carried.  The chunk axis is the **last grid
dimension** (sequential on TPU), so the carry lives in VMEM scratch —
the same state-in-scratch pattern as the flash kernel's online softmax,
which is exactly how a GPU "recurrence" maps onto the TPU grid model.

Stabilization matches the xLSTM paper (max-gate subtraction); numerics are
validated against the sequential oracle (ref.py) and against the model's
chunked jnp form (ref.mlstm_chunked) in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning_compiler_params

NEG_INF = -1e30
# The [P, P] carry, the [C, C] gate tiles and the f32 [C, P] operands
# outgrow Mosaic's default 16 MiB scoped VMEM from chunk 512 at P=1024;
# 100 MiB of a v5e core's 128 MiB compiles every chunk up to 2048 there.
VMEM_LIMIT_BYTES = 100 << 20


def _kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, o_ref,
            c_ref, n_ref, m_ref, *, chunk: int):
    t = pl.program_id(1)          # chunk index (sequential)

    @pl.when(t == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    q = q_ref[0].astype(jnp.float32)          # [C, P]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    li_c = li_ref[0]                          # [C, 1] gate columns (f32)
    lf_c = lf_ref[0]

    # The scalar stabilizer is kept as a [1, P] row (Mosaic cannot
    # broadcast a [1, 1] value along sublanes and lanes at once).
    m_prev_p = m_ref[0:1, :]                  # [1, P]
    m_prev = m_prev_p[:, 0:1]                 # [1, 1]
    c_prev = c_ref[...]                       # [P, P]
    n_prev = n_ref[0:1, :]                    # [1, P]

    # Every vector stays 2-D and no transpose is needed: row forms come
    # from masked sublane reductions of the gate columns, the inclusive
    # prefix sum from a masked lane reduction (ii: row, jj: column).
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = jj <= ii
    li_r = jnp.sum(jnp.where(ii == jj, li_c, 0.0), axis=0, keepdims=True)
    lf_r = jnp.sum(jnp.where(ii == jj, lf_c, 0.0), axis=0, keepdims=True)
    cum_c = jnp.sum(jnp.where(lower, lf_r, 0.0), axis=1, keepdims=True)
    cum_r = jnp.sum(jnp.where(ii <= jj, lf_c, 0.0), axis=0, keepdims=True)

    # D[i, j] = cum_i - cum_j + li_j  for j <= i
    d_mat = jnp.where(lower, cum_c - cum_r + li_r, NEG_INF)   # [C, C]
    m_loc = jnp.max(d_mat, axis=1, keepdims=True)             # [C, 1]
    m_comb = jnp.maximum(jnp.maximum(m_loc, cum_c + m_prev), NEG_INF)
    w = jnp.exp(d_mat - m_comb)                               # [C, C]

    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    s = qk * w
    h_intra = jnp.dot(s, v, preferred_element_type=jnp.float32)   # [C, P]
    n_intra = jnp.dot(w, k, preferred_element_type=jnp.float32)   # [C, P]

    scale_in = jnp.exp(cum_c + m_prev - m_comb)               # [C, 1]
    h_inter = jnp.dot(q, c_prev,
                      preferred_element_type=jnp.float32) * scale_in
    n_all = n_intra + n_prev * scale_in
    denom = jnp.maximum(jnp.abs(jnp.sum(n_all * q, axis=1, keepdims=True)),
                        jnp.exp(-m_comb))
    o_ref[0] = ((h_intra + h_inter) / denom).astype(o_ref.dtype)

    # ---- carry update ------------------------------------------------------
    rows = (chunk, n_prev.shape[1])
    total_p = jnp.sum(jnp.broadcast_to(lf_c, rows), axis=0,
                      keepdims=True)                          # [1, P]
    m_new_p = total_p + jnp.maximum(
        m_prev_p, jnp.max(jnp.broadcast_to(li_c - cum_c, rows), axis=0,
                          keepdims=True))
    decay = jnp.exp(total_p + m_prev_p - m_new_p)             # [1, P]
    wk = jnp.exp((total_p - m_new_p)[:, 0:1] - cum_c + li_c)  # [C, 1]
    kw = k * wk
    c_ref[...] = c_prev * decay + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_new = n_prev * decay + jnp.sum(kw, axis=0, keepdims=True)
    n_ref[...] = jnp.broadcast_to(n_new, n_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new_p, m_ref.shape)


def mlstm_chunk_fwd(q, k, v, logi, logf, *, chunk: int = 256,
                    num_warps=None, pipeline=None,
                    interpret: bool = False):
    """q/k/v [BH, S, P]; logi/logf [BH, S, 1] (f32); S % chunk == 0.

    k must already carry the 1/sqrt(P) scale.  Returns h [BH, S, P] (q.dtype).
    The gates are columns so that their (chunk, 1) block meets the TPU
    tiling rule for any chunk that is a multiple of 8 (16 for bf16 q/k/v).
    ``num_warps``/``pipeline`` are the GPU scheduling knobs (inert on
    TPU/interpret).
    """
    BH, S, P = q.shape
    assert S % chunk == 0, "chunk must divide sequence length"
    n_chunks = S // chunk

    kernel = functools.partial(_kernel, chunk=chunk)
    seq_spec = pl.BlockSpec((1, chunk, P), lambda b, t: (b, t, 0))
    gate_spec = pl.BlockSpec((1, chunk, 1), lambda b, t: (b, t, 0))
    extra = {}
    cp = tuning_compiler_params(num_warps, pipeline, interpret,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)
    if cp is not None:
        extra["compiler_params"] = cp
    return pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[seq_spec, seq_spec, seq_spec, gate_spec, gate_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, P), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((P, P), jnp.float32),      # matrix memory C
            pltpu.VMEM((8, P), jnp.float32),      # normalizer n (row-repl.)
            pltpu.VMEM((8, P), jnp.float32),      # stabilizer m (row-repl.)
        ],
        interpret=interpret,
        **extra,
    )(q, k, v, logi, logf)
