"""Train-step factory: microbatched grad accumulation under RunConfig knobs.

``make_train_step(model, rc)`` returns a pure ``step_fn(state, batch)``
suitable for ``jax.jit`` under a mesh (launch/train.py supplies the
shardings).  Knobs that shape the compiled program:

* ``microbatch``                — grad-accumulation split (scan or unrolled);
* ``remat_policy``              — applied inside the model backbone;
* ``grad_allreduce_dtype``      — gradients cast to bf16 *before* the
  cross-replica reduction (visible as halved all-reduce bytes in HLO);
* ``allreduce_per_microbatch``  — reduce inside the accumulation loop so
  XLA overlaps microbatch i's reduction with i+1's compute, instead of one
  bulk reduction at the end;
* ``optimizer`` family          — AdamW / Adafactor (train/optimizer.py).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.runconfig import RunConfig
from repro.train import optimizer as opt


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


def init_state(model: Model, rng, rc: RunConfig) -> TrainState:
    params = model.init(rng)
    return TrainState(params, opt.opt_init(params, rc),
                      jnp.zeros((), jnp.int32))


def state_axes(model: Model, rc: RunConfig) -> TrainState:
    pax = model.param_axes()
    return TrainState(pax, opt.opt_state_axes(pax, rc), ())


def _split_micro(batch: Dict[str, jnp.ndarray], n_micro: int):
    """[B, ...] -> [n_micro, B/n_micro, ...] per batch leaf.

    ``positions`` (M-RoPE ids) is [3, B, S]: its batch dim is axis 1.
    """
    out = {}
    for key, x in batch.items():
        if key == "positions":
            b = x.shape[1]
            x = x.reshape((x.shape[0], n_micro, b // n_micro) + x.shape[2:])
            out[key] = jnp.moveaxis(x, 1, 0)
        else:
            b = x.shape[0]
            out[key] = x.reshape((n_micro, b // n_micro) + x.shape[1:])
    return out


def make_train_step(model: Model, rc: RunConfig,
                    lr_schedule: Callable = None,
                    batch_size: int = None):
    """Build the jit-able step function for this (model, RunConfig)."""
    lr_schedule = lr_schedule or opt.cosine_schedule(
        rc.learning_rate, warmup=100, total=10_000)

    grad_dtype = jnp.bfloat16 if rc.grad_allreduce_dtype == "bfloat16" \
        else jnp.float32

    def loss_fn(params, mb):
        loss, metrics = model.loss(params, mb, rc)
        return loss, metrics

    def step_fn(state: TrainState, batch: Dict[str, jnp.ndarray]):
        b = batch["tokens"].shape[0]
        # rc.microbatch is PER-REPLICA: under jit, shapes are global, so
        # the number of accumulation steps is per_replica // microbatch
        # (dp read from the ambient mesh at trace time; 1 on a bare host).
        from repro.parallel.sharding import data_parallel_size
        dp = data_parallel_size(rc.shard)
        per_replica = max(b // dp, 1)
        micro = rc.microbatch if rc.microbatch > 0 else per_replica
        n_micro = max(per_replica // min(micro, per_replica), 1)
        n_micro = min(n_micro, b)            # b must split into n_micro

        with jax.named_scope("train.grad"):
            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

            if n_micro == 1 or b % n_micro != 0:
                (loss, metrics), grads = grad_fn(state.params, batch)
                grads = jax.tree.map(lambda g: g.astype(grad_dtype), grads)
            else:
                mbs = _split_micro(batch, n_micro)

                def accum(carry, mb):
                    g_acc, l_acc = carry
                    (loss, _), g = grad_fn(state.params, mb)
                    g = jax.tree.map(lambda x: x.astype(grad_dtype), g)
                    # per-microbatch reduction: accumulate in the (possibly
                    # compressed) reduction dtype right away — the pattern XLA
                    # overlaps; bulk mode accumulates f32 and casts at the end.
                    if rc.allreduce_per_microbatch:
                        g_acc = jax.tree.map(lambda a, x: a + x, g_acc, g)
                    else:
                        g_acc = jax.tree.map(
                            lambda a, x: a + x.astype(jnp.float32), g_acc, g)
                    return (g_acc, l_acc + loss), None

                acc_dtype = grad_dtype if rc.allreduce_per_microbatch \
                    else jnp.float32
                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype),
                                  state.params)
                if rc.grad_accum_unroll:
                    carry = (g0, jnp.zeros((), jnp.float32))
                    for i in range(n_micro):
                        mb = jax.tree.map(lambda x: x[i], mbs)
                        carry, _ = accum(carry, mb)
                    grads_sum, loss_sum = carry
                else:
                    (grads_sum, loss_sum), _ = jax.lax.scan(
                        accum, (g0, jnp.zeros((), jnp.float32)), mbs)
                grads = jax.tree.map(
                    lambda g: (g.astype(jnp.float32) / n_micro).astype(grad_dtype),
                    grads_sum)
                loss = loss_sum / n_micro
                metrics = {"nll": loss, "aux": jnp.zeros((), jnp.float32)}

        lr = lr_schedule(state.step)
        with jax.named_scope("train.update"):
            new_params, new_opt = opt.opt_update(grads, state.opt_state,
                                                 state.params, rc, lr)
            gnorm = opt.global_norm(grads)
        out_metrics = {"loss": loss.astype(jnp.float32),
                       "grad_norm": gnorm, "lr": lr,
                       **{k: v.astype(jnp.float32)
                          for k, v in metrics.items()}}
        return TrainState(new_params, new_opt, state.step + 1), out_metrics

    return step_fn
