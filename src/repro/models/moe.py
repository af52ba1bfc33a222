"""Mixture-of-Experts MLP with shared experts and top-k routing.

Covers grok-1 (8e top-2, gelu), qwen2-moe (60e top-4 + 4 shared, silu),
jamba (16e top-2) and LFM2-8B-A1B (32e top-4, sigmoid scores with an
expert bias used for the selection only).  Expert weights carry the
"experts" logical axis so the layout knob can place them on the model mesh
axis (expert parallelism).

The layer holds ``n_experts`` experts: the block that starts at
``expert_offset`` of the ``router_width`` experts the router scores (by
default all of them).  It routes every token over all ``router_width``
and computes only the part of the result that its own experts give —
one chip's share under expert parallelism, without the exchange.

Three implementations (``moe_impl`` knob, C3-gated), one routing and one
held set:

* ``dense``    — einsum over every held expert on every token, routing
  weights masked to the top-k.  No token dropping; it does
  router_width / k times the routed work.
* ``dropping`` — capacity-factor dispatch (one-hot scatter into
  [experts, capacity] buffers), the Switch-style layer whose work scales
  with top-k; pairs past an expert's capacity are dropped.  The capacity
  factor is a tuned knob.
* ``grouped``  — dropless: the (token, slot) pairs that chose a held
  expert are sorted by expert and run through the grouped-matmul Pallas
  kernel (``kernels/grouped_matmul``), so the rows computed follow the
  routing, rounded up to row tiles; each row is weighted and added back
  to its token.  No capacity, no dropped pair.  Rows move by gathers
  alone, forward and backward: the sorted order is a permutation of the
  pairs and each token owns K of them, so the dispatch gathers token rows
  by the order, the combine gathers each token's K expert rows by the
  inverse permutation and sums them, and each one's transpose is the
  other's gather (one custom VJP over dispatch, experts and combine).

Scopes inside the caller's ``model.moe``: ``route``, ``dispatch`` (the
sort, its inverse and the token rows' gather), ``experts`` (the products)
and ``combine`` (the weighted gather and sum).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import mlp
from repro.models.common import activation, reduce_dtype, trunc_normal
from repro.models.config import ModelConfig
from repro.runconfig import RunConfig


def _expert_ff(cfg: ModelConfig) -> int:
    return cfg.moe_d_ff if cfg.moe_d_ff is not None else cfg.d_ff


def init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    kr, ke, ks = jax.random.split(rng, 3)
    d, f, e = cfg.d_model, _expert_ff(cfg), cfg.n_experts
    n_mat = 3 if cfg.act == "silu" else 2
    keys = jax.random.split(ke, n_mat)
    p = {
        "router": {"w": trunc_normal(kr, (d, cfg.router_width), 1.0,
                                     jnp.float32)},
        "experts": {},
    }
    if cfg.expert_bias:
        # a buffer: it takes no gradient (``_routing``)
        p["router"]["bias"] = jnp.zeros((cfg.router_width,), jnp.float32)
    if cfg.act == "silu":
        p["experts"] = {
            "gate": trunc_normal(keys[0], (e, d, f), 1.0, dtype),
            "up": trunc_normal(keys[1], (e, d, f), 1.0, dtype),
            "down": trunc_normal(keys[2], (e, f, d), 1.0, dtype),
        }
    else:
        p["experts"] = {
            "up": trunc_normal(keys[0], (e, d, f), 1.0, dtype),
            "down": trunc_normal(keys[1], (e, f, d), 1.0, dtype),
        }
    if cfg.n_shared_experts:
        # Shared experts act as one dense MLP of width n_shared * f.
        p["shared"] = mlp.init(ks, cfg, d_ff=cfg.n_shared_experts * f, dtype=dtype)
    return p


def axes(cfg: ModelConfig):
    ax = {
        "router": {"w": ("embed", "experts")},
        "experts": {},
    }
    if cfg.expert_bias:
        ax["router"]["bias"] = (None,)
    if cfg.act == "silu":
        ax["experts"] = {
            "gate": ("experts", "expert_in", "expert_ff"),
            "up": ("experts", "expert_in", "expert_ff"),
            "down": ("experts", "expert_ff", "expert_in"),
        }
    else:
        ax["experts"] = {
            "up": ("experts", "expert_in", "expert_ff"),
            "down": ("experts", "expert_ff", "expert_in"),
        }
    if cfg.n_shared_experts:
        ax["shared"] = mlp.axes(cfg)
    return ax


def _routing(params, x, cfg: ModelConfig):
    """Route x [T, d] over all ``router_width`` experts.

    Returns (topi [T, k] int32, topv [T, k] f32 weights, aux loss).
    softmax: top-k of the probabilities, renormalized.  sigmoid: the
    selection is top-k of σ(logits) + bias (the bias is a buffer and takes
    no gradient), and each selected expert's weight is its σ over the
    selected ones' sum + 1e-6."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["router"]["w"])
    k = cfg.n_experts_per_tok
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        select = scores
        if cfg.expert_bias:
            select = scores + jax.lax.stop_gradient(params["router"]["bias"])
        _, topi = jax.lax.top_k(select, k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-6)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(scores, k)
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)  # renormalize
    aux = jnp.zeros((), jnp.float32)
    if cfg.router_aux_coef:
        # Switch-style load-balancing auxiliary loss over every expert.
        chosen = jax.nn.one_hot(topi, cfg.router_width).sum(axis=1)
        frac_tokens = jnp.mean((chosen > 0).astype(jnp.float32), axis=0)
        frac_probs = jnp.mean(scores, axis=0)
        aux = cfg.router_width * jnp.sum(frac_tokens * frac_probs)
    return topi, topv, aux


def _held(topi, cfg: ModelConfig):
    """(local expert id of each (token, slot) pair, n_experts where the
    expert is held elsewhere [T, k]; pairs per held expert [n_experts])."""
    E = cfg.n_experts
    local = topi - cfg.expert_offset
    local = jnp.where((local >= 0) & (local < E), local, E)
    # a compare-and-sum, not a scatter into E + 1 bins: every pair would
    # collide on a handful of addresses
    sizes = jnp.sum(local.reshape(-1, 1) == jnp.arange(E + 1), axis=0,
                    dtype=jnp.int32)
    return local, sizes


def _expert_hidden(ep, h, act_name: str):
    """h [E, C, d] -> activated hidden z [E, C, f] (per-expert up/gate)."""
    act = activation(act_name)
    if "gate" in ep:
        g = jnp.einsum("ecd,edf->ecf", h, ep["gate"],
                       preferred_element_type=jnp.float32).astype(h.dtype)
        u = jnp.einsum("ecd,edf->ecf", h, ep["up"],
                       preferred_element_type=jnp.float32).astype(h.dtype)
        return act(g) * u
    u = jnp.einsum("ecd,edf->ecf", h, ep["up"],
                   preferred_element_type=jnp.float32).astype(h.dtype)
    return act(u)


def _expert_mlp(ep, h, act_name: str):
    """h [E, C, d] through per-expert weights [E, d, f] / [E, f, d]."""
    z = _expert_hidden(ep, h, act_name)
    return jnp.einsum("ecf,efd->ecd", z, ep["down"],
                      preferred_element_type=jnp.float32).astype(h.dtype)


def apply(params, x, cfg: ModelConfig, rc: RunConfig
          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x [B, S, d] -> (y [B, S, d], stats): ``aux`` (the weighted aux
    loss), ``moe_held_rows`` (pairs routed to a held expert) and
    ``moe_max_group`` (the most pairs one held expert received)."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    with jax.named_scope("route"):
        topi, topv, aux = _routing(params, xt, cfg)
        local, sizes = _held(topi, cfg)

    if rc.moe_impl == "dense":
        y = _dense(params, xt, local, topv, cfg, rc)
    elif rc.moe_impl == "dropping":
        y = _capacity_dispatch(params, xt, local, topv, cfg, rc)
    elif rc.moe_impl == "grouped":
        y = _grouped(params, xt, local, topv, sizes, cfg)
    else:
        raise ValueError(rc.moe_impl)

    if cfg.n_shared_experts:
        y = y + mlp.apply(params["shared"], xt, cfg, rc)
    stats = {"aux": aux * cfg.router_aux_coef,
             "moe_held_rows": jnp.sum(sizes[:-1]).astype(jnp.float32),
             "moe_max_group": jnp.max(sizes[:-1]).astype(jnp.float32)}
    return y.reshape(B, S, d), stats


def _dense(params, xt, local, topv, cfg: ModelConfig, rc: RunConfig):
    """Every held expert on every token, masked combine — WITHOUT
    materializing the [E, T, d] token broadcast (qwen2-moe: 60 experts x
    15.7 GB per layer); the einsum broadcasts inside the dot for free."""
    E = cfg.n_experts
    with jax.named_scope("dispatch"):
        # [T, E]: each held expert's routing weight, zero where unchosen
        weights = jnp.sum(jax.nn.one_hot(local, E, dtype=topv.dtype)
                          * topv[..., None], axis=1)
    with jax.named_scope("experts"):
        ep = params["experts"]
        act = activation(cfg.act)
        if "gate" in ep:
            g = jnp.einsum("td,edf->etf", xt, ep["gate"],
                           preferred_element_type=jnp.float32).astype(xt.dtype)
            u = jnp.einsum("td,edf->etf", xt, ep["up"],
                           preferred_element_type=jnp.float32).astype(xt.dtype)
            z = act(g) * u
        else:
            u = jnp.einsum("td,edf->etf", xt, ep["up"],
                           preferred_element_type=jnp.float32).astype(xt.dtype)
            z = act(u)
        # Routing combine BEFORE the down-proj contraction: scaling z by
        # the routing weights is local/elementwise, and the (e, f) joint
        # contraction then emits ONE [T, d] partial sum per shard instead
        # of per-expert [E, T, d] partials (8x the all-reduce bytes —
        # measured 12.75 GiB/layer vs 0.8).  A 3-operand einsum does NOT
        # guarantee this order (opt_einsum picked the bad one).
        zs = z * weights.T[:, :, None].astype(z.dtype)        # [E, T, f]
        return jnp.einsum("etf,efd->td", zs, ep["down"],
                          preferred_element_type=reduce_dtype(rc)
                          ).astype(xt.dtype)


def _permutation(local, sizes):
    """The T·K (token, slot) pairs sorted by held expert, stably, pairs of
    no held expert last: ``order`` [T·K], the pair at each sorted position,
    and its inverse ``inv`` [T, K], each pair's sorted position.

    ``inv`` needs no second sort and no scatter: a pair's position is the
    rows of the experts before its own plus the pairs of its own expert
    before it, an exclusive cumulative sum over a one-hot [T·K, E + 1]."""
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    onehot = (flat[:, None] == jnp.arange(sizes.shape[0])).astype(jnp.int32)
    before = jnp.cumsum(onehot, axis=0) - onehot + (jnp.cumsum(sizes) - sizes)
    inv = jnp.sum(onehot * before, axis=1).reshape(local.shape)
    return order, inv


def _experts(act_name: str, xs, ep, sizes):
    """Sorted rows xs [T·K, d] through their held experts' grouped
    products; zero rows past the held groups."""
    from repro.kernels.grouped_matmul.ops import grouped_matmul
    act = activation(act_name)
    if "gate" in ep:
        h = act(grouped_matmul(xs, ep["gate"], sizes)) \
            * grouped_matmul(xs, ep["up"], sizes)
    else:
        h = act(grouped_matmul(xs, ep["up"], sizes))
    return grouped_matmul(h, ep["down"], sizes)


def _grouped(params, xt, local, topv, sizes, cfg: ModelConfig):
    """Dropless: the pairs sorted by held expert through the grouped-matmul
    kernel; rows of experts held elsewhere sort last and are not
    computed."""
    with jax.named_scope("dispatch"):
        order, inv = _permutation(local, sizes)
    with jax.named_scope("combine"):
        # a held-elsewhere pair weighs exactly 0, whatever its row holds
        w = jnp.where(local < cfg.n_experts, topv, 0.0)
    return _dropless(cfg.act, xt, w, params["experts"], sizes, order, inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dropless(act_name, xt, w, ep, sizes, order, inv):
    """Dispatch, experts and combine: y[t] = Σ_k w[t, k] · ys[inv[t, k]]
    for ys the experts' rows of xt[order // K].  Every move of rows, in
    both directions, is a gather by ``order`` or by ``inv``."""
    return _dropless_fwd(act_name, xt, w, ep, sizes, order, inv)[0]


def _dropless_fwd(act_name, xt, w, ep, sizes, order, inv):
    K = inv.shape[1]
    with jax.named_scope("dispatch"):
        xs = xt[order // K]                                      # [T*K, d]
    with jax.named_scope("experts"):
        ys, experts_vjp = jax.vjp(
            lambda xs, ep: _experts(act_name, xs, ep, sizes), xs, ep)
    with jax.named_scope("combine"):
        y = jnp.sum(w[..., None] * ys[inv].astype(jnp.float32), axis=1)
    return y.astype(xt.dtype), (w, order, inv, ys, experts_vjp)


def _dropless_bwd(act_name, res, dy):
    w, order, inv, ys, experts_vjp = res
    K = inv.shape[1]
    with jax.named_scope("combine"):
        # dy's row for each sorted pair: it gives the rows' gradient and,
        # against the rows themselves, the weights'
        dyo = dy.astype(jnp.float32)[order // K]                 # [T*K, d]
        dys = (w.reshape(-1)[order][:, None] * dyo).astype(ys.dtype)
        dw = jnp.sum(dyo * ys.astype(jnp.float32), axis=1)[inv]  # [T, K]
        dw = dw.astype(w.dtype)
    with jax.named_scope("experts"):
        dxs, dep = experts_vjp(dys)
    with jax.named_scope("dispatch"):
        dxt = jnp.sum(dxs[inv].astype(jnp.float32), axis=1)
    return dxt.astype(dxs.dtype), dw, dep, None, None, None


_dropless.defvjp(_dropless_fwd, _dropless_bwd)


def _capacity_dispatch(params, xt, local, topv, cfg: ModelConfig,
                       rc: RunConfig):
    """Switch-style capacity-factor dispatch (token dropping).  Each held
    expert's buffer holds the capacity factor times its expected load,
    T·k / router_width pairs."""
    T, d = xt.shape
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    with jax.named_scope("dispatch"):
        capacity = max(1, int(rc.moe_capacity_factor * T * K
                              / cfg.router_width))
        capacity = min(capacity, T)

        # position of each (token, k) inside its expert's buffer (pairs
        # of experts held elsewhere are one-hot zero and never kept)
        onehot = jax.nn.one_hot(local, E, dtype=jnp.int32)    # [T, K, E]
        flat = onehot.reshape(T * K, E)
        pos_in_expert = jnp.cumsum(flat, axis=0) - flat       # [T*K, E]
        pos = jnp.sum(pos_in_expert * flat, axis=-1).reshape(T, K)
        keep = (pos < capacity) & (local < E)                 # [T, K]

        # scatter tokens into [E, capacity, d]
        eidx = jnp.minimum(local, E - 1).reshape(-1)          # [T*K]
        cidx = jnp.where(keep.reshape(-1), pos.reshape(-1), capacity)
        buf = jnp.zeros((E, capacity + 1, d), xt.dtype)
        tok = jnp.repeat(xt, K, axis=0)                       # [T*K, d]
        buf = buf.at[eidx, cidx].add(tok)
        buf = buf[:, :capacity]                               # [E, C, d]

    with jax.named_scope("experts"):
        y_buf = _expert_mlp(params["experts"], buf, cfg.act)  # [E, C, d]

    with jax.named_scope("combine"):
        safe_c = jnp.minimum(cidx, capacity - 1)
        gathered = y_buf[eidx, safe_c]                        # [T*K, d]
        w = (topv.reshape(-1, 1) * keep.reshape(-1, 1)).astype(xt.dtype)
        return jnp.sum((gathered * w).reshape(T, K, d), axis=1)
