"""Composable decoder LM over the repeating block pattern.

The layer stack is ``lax.scan`` over *pattern groups*: parameters for each
position in the repeating pattern are stacked over groups, so an 80-layer
homogeneous model compiles one layer body, and jamba's 72 layers compile
one 8-layer group body.  Remat policy (SAPPHIRE knob) wraps the group body.

Exposes:
    init / axes            — parameters and logical sharding axes
    forward                — full-sequence logits (train / prefill)
    loss_fn                — next-token cross entropy (+ MoE aux)
    init_decode_state      — per-position stacked caches / states
    prefill                — fill caches from a prompt, return state
    decode_step            — one-token step through the whole stack
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, conv, mlp, moe, ssm, xlstm
from repro.models.common import (dense_apply, norm_apply, norm_axes,
                                 norm_init, stack_axes, stack_init, trunc_normal)
from repro.models.config import (ATTN, CONV, MAMBA, MLP_DENSE, MLP_MOE,
    MLP_NONE, MLSTM, SLSTM, LayerSpec, ModelConfig)
from jax.ad_checkpoint import checkpoint_name

from repro.runconfig import RunConfig


# ---------------------------------------------------------------------------
# per-position init / axes
# ---------------------------------------------------------------------------

def _pos_init(rng, spec: LayerSpec, cfg: ModelConfig, dtype):
    k1, k2, k3 = jax.random.split(rng, 3)
    p: Dict[str, Any] = {}
    if spec.kind == ATTN:
        p["norm1"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["attn"] = attention.init(k1, cfg, dtype)
    elif spec.kind == MAMBA:
        p["norm1"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["mamba"] = ssm.init(k1, cfg, dtype)
    elif spec.kind == MLSTM:
        p["norm1"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["mlstm"] = xlstm.mlstm_init(k1, cfg, dtype)
    elif spec.kind == SLSTM:
        p["norm1"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["slstm"] = xlstm.slstm_init(k1, cfg, dtype)
    elif spec.kind == CONV:
        p["norm1"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["conv"] = conv.init(k1, cfg, dtype)
    else:
        raise ValueError(spec.kind)
    if spec.mlp == MLP_DENSE:
        p["norm2"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["mlp"] = mlp.init(k2, cfg, dtype=dtype)
    elif spec.mlp == MLP_MOE:
        p["norm2"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["moe"] = moe.init(k2, cfg, dtype)
    return p


def _pos_axes(spec: LayerSpec, cfg: ModelConfig):
    ax: Dict[str, Any] = {}
    if spec.kind == ATTN:
        ax["norm1"] = norm_axes(cfg.norm)
        ax["attn"] = attention.axes(cfg)
    elif spec.kind == MAMBA:
        ax["norm1"] = norm_axes(cfg.norm)
        ax["mamba"] = ssm.axes(cfg)
    elif spec.kind == MLSTM:
        ax["norm1"] = norm_axes(cfg.norm)
        ax["mlstm"] = xlstm.mlstm_axes(cfg)
    elif spec.kind == SLSTM:
        ax["norm1"] = norm_axes(cfg.norm)
        ax["slstm"] = xlstm.slstm_axes(cfg)
    elif spec.kind == CONV:
        ax["norm1"] = norm_axes(cfg.norm)
        ax["conv"] = conv.axes(cfg)
    if spec.mlp == MLP_DENSE:
        ax["norm2"] = norm_axes(cfg.norm)
        ax["mlp"] = mlp.axes(cfg)
    elif spec.mlp == MLP_MOE:
        ax["norm2"] = norm_axes(cfg.norm)
        ax["moe"] = moe.axes(cfg)
    return ax


def init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    params: Dict[str, Any] = {
        "embed": {"tok": trunc_normal(k_emb, (cfg.vocab_size, cfg.d_model),
                                      1.0, dtype)},
        "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
        "layers": [],
    }
    pk = jax.random.split(k_layers, len(cfg.pattern))
    for p_i, spec in enumerate(cfg.pattern):
        params["layers"].append(
            stack_init(pk[p_i], cfg.n_groups,
                       lambda r, s=spec: _pos_init(r, s, cfg, dtype)))
    if not cfg.tie_embeddings:
        params["head"] = {"w": trunc_normal(
            k_head, (cfg.d_model, cfg.vocab_size), 1.0, dtype)}
    return params


def axes(cfg: ModelConfig):
    ax: Dict[str, Any] = {
        "embed": {"tok": ("vocab", "emb_embed")},
        "final_norm": norm_axes(cfg.norm),
        "layers": [stack_axes(_pos_axes(spec, cfg)) for spec in cfg.pattern],
    }
    if not cfg.tie_embeddings:
        ax["head"] = {"w": ("emb_embed", "vocab")}
    return ax


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

_MLP_SCOPE = {MLP_DENSE: "model.mlp", MLP_MOE: "model.moe"}


def aux_zero(cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    """The stack's auxiliary outputs, summed over its layers: the MoE aux
    loss (``aux``, weighted) and, where the stack has MoE layers, the
    counters of :func:`repro.models.moe.apply`."""
    names = ("aux", "moe_held_rows", "moe_max_group") if cfg.has_moe \
        else ("aux",)
    return {k: jnp.zeros((), jnp.float32) for k in names}


def _block_forward(spec: LayerSpec, p, x, positions, cfg: ModelConfig,
                   rc: RunConfig):
    """One block (pre-norm residual).  Returns (x, aux) (:func:`aux_zero`).

    The mixer and the MLP branch each run under a ``jax.named_scope``
    (``model.<kind>``, ``model.mlp`` / ``model.moe``): it names their
    operations, forward and backward, in the compiled program's metadata
    and so in a profile."""
    aux = aux_zero(cfg)
    with jax.named_scope(f"model.{spec.kind}"):
        h = norm_apply(p["norm1"], x, kind=cfg.norm, eps=cfg.norm_eps)
        if spec.kind == ATTN:
            h = attention.apply(p["attn"], h, positions, cfg, rc,
                                causal=True, window=spec.sliding_window)
        elif spec.kind == MAMBA:
            h = ssm.apply(p["mamba"], h, cfg, rc)
        elif spec.kind == MLSTM:
            h = xlstm.mlstm_apply(p["mlstm"], h, cfg, rc)
        elif spec.kind == SLSTM:
            h = xlstm.slstm_apply(p["slstm"], h, cfg, rc)
        elif spec.kind == CONV:
            h = conv.apply(p["conv"], h, cfg, rc)
        x = x + h
    if spec.mlp == MLP_NONE:
        return x, aux
    with jax.named_scope(_MLP_SCOPE[spec.mlp]):
        h = norm_apply(p["norm2"], x, kind=cfg.norm, eps=cfg.norm_eps)
        if spec.mlp == MLP_DENSE:
            x = x + mlp.apply(p["mlp"], h, cfg, rc)
        else:
            y, aux = moe.apply(p["moe"], h, cfg, rc)
            x = x + y
    return x, aux


def _remat_wrap(fn, rc: RunConfig):
    if rc.remat_policy == "none":
        return fn
    if rc.remat_policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if rc.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if rc.remat_policy == "block":
        # save ONLY the named bf16 carry: without the explicit name, the
        # partial-eval saves the f32 *convert* of x (first reuse site is
        # the f32 norm), doubling the residual stack and forcing
        # whole-stack convert round-trips every scan iteration
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "block_input"))
    raise ValueError(rc.remat_policy)


def backbone(params, x, positions, cfg: ModelConfig, rc: RunConfig):
    """Embedded activations -> final hidden states.  x [B,S,d]."""
    from repro.parallel.sharding import (gather_weights_for_compute,
                                         shard_activation)
    # axes of ONE scan slice (scan strips the stacked group dim)
    pattern_axes = [_pos_axes(spec, cfg) for spec in cfg.pattern]

    act_dtype = jnp.bfloat16 if rc.activation_dtype == "bfloat16" \
        else jnp.float32

    def group_body(carry, layer_slice):
        x, aux = carry
        # pin BOTH layout and dtype of the carried activation: the layout
        # pin stops SPMD replicating the batch inside the scan (dp×
        # redundant attention); the dtype pin keeps the remat-saved
        # residual stack in bf16 (a single f32 slice forces XLA to
        # convert the WHOLE [L,B,S,d] stack round-trip every iteration)
        x = x.astype(act_dtype)
        x = shard_activation(x, ("batch", "seq", "embed"), rc.shard)
        x = checkpoint_name(x, "block_input")
        for p_i, spec in enumerate(cfg.pattern):
            # ZeRO-3: stream this position's weights in (all-gather over
            # data) instead of partial-sum matmuls + activation all-reduce
            p = gather_weights_for_compute(layer_slice[p_i],
                                           pattern_axes[p_i], rc.shard)
            x, a = _block_forward(spec, p, x, positions, cfg, rc)
            aux = jax.tree.map(jnp.add, aux, a)
        return (x.astype(act_dtype), aux), None

    body = _remat_wrap(group_body, rc)
    (x, aux), _ = jax.lax.scan(body, (x, aux_zero(cfg)), params["layers"])
    x = norm_apply(params["final_norm"], x, kind=cfg.norm, eps=cfg.norm_eps)
    return x, aux


def embed(params, tokens, cfg: ModelConfig):
    with jax.named_scope("model.embed"):
        x = params["embed"]["tok"][tokens]
        if cfg.embedding_multiplier:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def unembed(params, x, cfg: ModelConfig, rc: Optional[RunConfig] = None):
    from repro.models.common import dense_apply, reduce_dtype
    with jax.named_scope("model.head"):
        if cfg.tie_embeddings:
            w = params["embed"]["tok"].T
        else:
            w = params["head"]["w"]
        if rc is not None and reduce_dtype(rc) == jnp.bfloat16:
            # vocab-sharded head: bwd dgrad AR in bf16
            return dense_apply({"w": w}, x, preferred=jnp.bfloat16) \
                .astype(jnp.float32)
        return jnp.einsum("bsd,dv->bsv", x, w,
                          preferred_element_type=jnp.float32)


def forward(params, tokens, cfg: ModelConfig, rc: RunConfig,
            positions: Optional[jnp.ndarray] = None):
    """tokens [B,S] int32 -> logits [B,S,V] f32."""
    from repro.parallel.sharding import shard_activation
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = embed(params, tokens, cfg)
    x = shard_activation(x, ("batch", "seq", "embed"), rc.shard)
    x, aux = backbone(params, x, positions, cfg, rc)
    logits = unembed(params, x, cfg, rc)
    return shard_activation(logits, ("batch", "seq", "vocab"), rc.shard), aux


def loss_fn(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            rc: RunConfig):
    """Next-token cross-entropy.  batch: tokens [B,S], labels [B,S]."""
    logits, aux = forward(params, batch["tokens"], cfg, rc,
                          positions=batch.get("positions"))
    labels = batch["labels"]
    # the cross-entropy shares the head's scope with the unembedding
    with jax.named_scope("model.head"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction instead of gather: partial-sums cleanly over a
        # vocab-sharded (model-axis) logits tensor in SPMD.
        onehot = jax.nn.one_hot(labels, cfg.vocab_size, dtype=logits.dtype)
        ll = jnp.einsum("bsv,bsv->bs", logits, onehot)
        nll = jnp.mean(logz - ll)
    return nll + aux["aux"], {"nll": nll, **aux}


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """Per-pattern-position stacked caches/states + current length."""
    slots: Tuple[Any, ...]      # one entry per pattern position
    pos: jnp.ndarray            # [B] int32: tokens consumed per slot
                                # (vector so continuous batching can run
                                # every slot at its own position)


def _pos_state(spec: LayerSpec, batch: int, s_max: int, cfg: ModelConfig,
               rc: RunConfig):
    if spec.kind == ATTN:
        return attention.init_cache(batch, s_max, cfg, rc)
    if spec.kind == MAMBA:
        return ssm.init_state(batch, cfg)
    if spec.kind == MLSTM:
        return xlstm.mlstm_init_state(batch, cfg)
    if spec.kind == SLSTM:
        return xlstm.slstm_init_state(batch, cfg)
    if spec.kind == CONV:
        return conv.init_state(batch, cfg)
    raise ValueError(spec.kind)


def _pos_state_axes(spec: LayerSpec, cfg: ModelConfig, rc: RunConfig):
    if spec.kind == ATTN:
        return attention.cache_axes(rc)
    if spec.kind == MAMBA:
        return ssm.state_axes(cfg)
    if spec.kind == MLSTM:
        return xlstm.mlstm_state_axes(cfg)
    if spec.kind == SLSTM:
        return xlstm.slstm_state_axes(cfg)
    if spec.kind == CONV:
        return conv.state_axes(cfg)
    raise ValueError(spec.kind)


def init_decode_state(batch: int, s_max: int, cfg: ModelConfig,
                      rc: RunConfig) -> DecodeState:
    slots = []
    for spec in cfg.pattern:
        one = _pos_state(spec, batch, s_max, cfg, rc)
        stacked = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (cfg.n_groups,) + t.shape)
            if cfg.n_groups > 1 else t[None], one)
        slots.append(stacked)
    return DecodeState(slots=tuple(slots),
                       pos=jnp.zeros((batch,), jnp.int32))


def decode_state_axes(cfg: ModelConfig, rc: RunConfig) -> DecodeState:
    slots = []
    for spec in cfg.pattern:
        ax = _pos_state_axes(spec, cfg, rc)
        slots.append(stack_axes(ax))
    return DecodeState(slots=tuple(slots), pos=("batch",))


# ---------------------------------------------------------------------------
# decode step (and prefill)
# ---------------------------------------------------------------------------

def _block_decode(spec: LayerSpec, p, x, slot, pos, cfg: ModelConfig,
                  rc: RunConfig):
    h = norm_apply(p["norm1"], x, kind=cfg.norm, eps=cfg.norm_eps)
    if spec.kind == ATTN:
        ck, cv = slot
        h, ck, cv = attention.decode_apply(p["attn"], h, ck, cv, pos, cfg, rc,
                                           window=spec.sliding_window)
        slot = (ck, cv)
    elif spec.kind == MAMBA:
        h, slot = ssm.decode_step(p["mamba"], h, slot, cfg, rc)
    elif spec.kind == MLSTM:
        h, slot = xlstm.mlstm_decode_step(p["mlstm"], h, slot, cfg, rc)
    elif spec.kind == SLSTM:
        h, slot = xlstm.slstm_decode_step(p["slstm"], h, slot, cfg, rc)
    elif spec.kind == CONV:
        h, slot = conv.decode_step(p["conv"], h, slot, cfg, rc)
    x = x + h
    if spec.mlp == MLP_DENSE:
        h = norm_apply(p["norm2"], x, kind=cfg.norm, eps=cfg.norm_eps)
        x = x + mlp.apply(p["mlp"], h, cfg, rc)
    elif spec.mlp == MLP_MOE:
        h = norm_apply(p["norm2"], x, kind=cfg.norm, eps=cfg.norm_eps)
        y, _ = moe.apply(p["moe"], h, cfg, rc)
        x = x + y
    return x, slot


def decode_step(params, token, state: DecodeState, cfg: ModelConfig,
                rc: RunConfig):
    """token [B,1] int32 -> (logits [B,1,V], new state)."""
    x = embed(params, token, cfg)
    pos = state.pos

    from repro.parallel.sharding import shard_activation

    def group_body(x, xs):
        layer_slice, slot_slice = xs
        new_slots = []
        x = shard_activation(x, ("batch", "seq", "embed"), rc.shard)
        for p_i, spec in enumerate(cfg.pattern):
            x, s = _block_decode(spec, layer_slice[p_i], x, slot_slice[p_i],
                                 pos, cfg, rc)
            new_slots.append(s)
        return x, tuple(new_slots)

    x, new_slots = jax.lax.scan(group_body, x,
                                (params["layers"], state.slots))
    x = norm_apply(params["final_norm"], x, kind=cfg.norm, eps=cfg.norm_eps)
    logits = unembed(params, x, cfg)
    return logits, DecodeState(slots=new_slots, pos=pos + 1)


def prefill(params, tokens, s_max: int, cfg: ModelConfig, rc: RunConfig):
    """Run the prompt through the stack, filling caches.

    Returns (last-token logits [B,1,V], DecodeState at pos=S).
    Implemented as full-sequence forward + per-layer cache fill; SSM-family
    states are produced by a chunked pass (scan body reuses apply()).
    """
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = embed(params, tokens, cfg)
    state = init_decode_state(B, s_max, cfg, rc)

    from repro.parallel.sharding import (gather_weights_for_compute,
                                         shard_activation)
    pattern_axes = [_pos_axes(spec, cfg) for spec in cfg.pattern]

    def group_body(carry, xs):
        x = carry
        layer_slice, slot_slice = xs
        new_slots = []
        # same pins as the train backbone: batch sharding would otherwise
        # be dropped inside the scan (measured: fully replicated [B,S,d]
        # tiles in prefill)
        x = shard_activation(x, ("batch", "seq", "embed"), rc.shard)
        for p_i, spec in enumerate(cfg.pattern):
            p = gather_weights_for_compute(layer_slice[p_i],
                                           pattern_axes[p_i], rc.shard)
            slot = slot_slice[p_i]
            h = norm_apply(p["norm1"], x, kind=cfg.norm, eps=cfg.norm_eps)
            if spec.kind == ATTN:
                k, v = attention.project_kv(p["attn"], h, positions, cfg)
                ck = attention.fill_cache(slot[0], k, rc)
                cv = attention.fill_cache(slot[1], v, rc)
                a = attention.apply(p["attn"], h, positions, cfg, rc,
                                    causal=True, window=spec.sliding_window)
                x = x + a
                slot = (ck, cv)
            elif spec.kind == MAMBA:
                y, slot = _ssm_prefill(p["mamba"], h, slot, cfg, rc)
                x = x + y
            elif spec.kind == MLSTM:
                y, slot = _mlstm_prefill(p["mlstm"], h, cfg, rc)
                x = x + y
            elif spec.kind == SLSTM:
                y, slot = _slstm_prefill(p["slstm"], h, cfg, rc)
                x = x + y
            elif spec.kind == CONV:
                x = x + conv.apply(p["conv"], h, cfg, rc)
                slot = conv.final_state(p["conv"], h, cfg)
            if spec.mlp == MLP_DENSE:
                h = norm_apply(p["norm2"], x, kind=cfg.norm, eps=cfg.norm_eps)
                x = x + mlp.apply(p["mlp"], h, cfg, rc)
            elif spec.mlp == MLP_MOE:
                h = norm_apply(p["norm2"], x, kind=cfg.norm, eps=cfg.norm_eps)
                y, _ = moe.apply(p["moe"], h, cfg, rc)
                x = x + y
            new_slots.append(slot)
        return x, tuple(new_slots)

    x, new_slots = jax.lax.scan(group_body, x,
                                (params["layers"], state.slots))
    x = norm_apply(params["final_norm"], x, kind=cfg.norm, eps=cfg.norm_eps)
    logits = unembed(params, x[:, -1:], cfg)
    return logits, DecodeState(slots=new_slots,
                               pos=jnp.full((B,), S, jnp.int32))


def _ssm_prefill(p, h, slot, cfg, rc):
    """Sequence pass that also returns the final SSM state."""
    y = ssm.apply(p, h, cfg, rc)
    # final state: run the last conv window + rebuild carried state cheaply
    # via a dedicated scan (full fidelity; reuses decode_step over the tail
    # would be O(S) — instead recompute the state from the chunked pass).
    state = _ssm_final_state(p, h, cfg, rc)
    return y, state


def _ssm_final_state(p, h, cfg, rc) -> ssm.SsmState:
    B, S, _ = h.shape
    x, z, di, nh, N, P = ssm._project(p, h, cfg)
    xc = jax.nn.silu(ssm._causal_conv(x, p["conv_w"], p["conv_b"])
                     .astype(jnp.float32)).astype(x.dtype)
    bc = dense_apply(p["bc_proj"], xc)
    Bm, _ = jnp.split(bc, 2, axis=-1)
    dt, log_decay = ssm._gates(p, xc, nh)
    xh = xc.reshape(B, S, nh, P).astype(jnp.float32) * dt[..., None]
    cum = jnp.cumsum(log_decay, axis=1)                     # [B,S,H]
    total = cum[:, -1:, :]
    w = jnp.exp(total - cum)                                # [B,S,H]
    s = jnp.einsum("bsn,bshp->bhnp", Bm.astype(jnp.float32), xh * w[..., None])
    conv_tail = x[:, S - (cfg.ssm_conv_width - 1):, :].astype(jnp.bfloat16)
    return ssm.SsmState(s=s, conv=conv_tail)


def _mlstm_prefill(p, h, cfg, rc):
    y = xlstm.mlstm_apply(p, h, cfg, rc)
    state = _mlstm_final_state(p, h, cfg)
    return y, state


def _mlstm_final_state(p, h, cfg) -> xlstm.MlstmState:
    B, S, _ = h.shape
    di, nh, P = xlstm.mlstm_dims(cfg)
    q, k, v, logi, logf, z = xlstm._mlstm_qkvg(p, h, cfg)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    cum = jnp.cumsum(logf, axis=1)                          # [B,S,H]
    total = cum[:, -1, :]                                   # [B,H]
    scores = total[:, None, :] - cum + logi                 # [B,S,H]
    m = jnp.max(scores, axis=1)                             # [B,H]
    wk = jnp.exp(scores - m[:, None, :])
    c = jnp.einsum("bshp,bshr->bhpr", kf * wk[..., None], vf)
    n = jnp.einsum("bshp,bsh->bhp", kf, wk)
    return xlstm.MlstmState(c=c, n=n, m=m)


def _slstm_prefill(p, h, cfg, rc):
    B, S, d = h.shape
    x_gates = dense_apply({"w": p["w_in"]}, h)

    def step(state, t):
        state = xlstm._slstm_cell(p, x_gates[:, t], state, cfg)
        return state, state.h

    st0 = xlstm.slstm_init_state(B, cfg)
    st, hs = jax.lax.scan(step, st0, jnp.arange(S))
    hh = hs.transpose(1, 0, 2).astype(h.dtype)
    hh = norm_apply(p["out_norm"], hh, kind=cfg.norm, eps=cfg.norm_eps)
    y = dense_apply(p["ffn_down"],
                    jax.nn.gelu(dense_apply(p["ffn_up"], hh)
                                .astype(jnp.float32)).astype(hh.dtype))
    return y, st
