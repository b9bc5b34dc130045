"""ClientUpdate (paper Algorithm 1, lines 11-15).

A client receives the global model, runs ``local_steps`` optimizer steps on
its local batch (the paper uses exactly one SGD step — "after one time local
training"), and returns its local model. The update is a *pure deterministic*
function of (global params, client batch) — the property that lets the
scan engine train a client again for Eq. 5 instead of holding its local
(``server.scan_client_passes``).
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.partition import ParamPartition
from repro.optim import sgd
from repro.optim.opt import Optimizer

Pytree = Any
LossFn = Callable[[Pytree, dict], jnp.ndarray]


def make_local_update(loss_fn: LossFn, opt: Optimizer,
                      local_steps: int = 1, remat: bool = False,
                      partition: Optional[ParamPartition] = None):
    """Returns local_update(global_params, batch) -> (local_params, mean_loss).

    ``batch`` leaves are (b, ...) — the same batch is used for every local
    step (paper setting: local_steps=1 makes this exact; >1 approximates
    multi-epoch local training on the client's sampled data).

    ``remat=True`` wraps each local step in ``jax.checkpoint`` so forward
    activations are recomputed in the backward pass — useful when the whole
    FL schedule is one ``lax.scan`` (run_training_scan) and K stacked
    clients × local activations would otherwise set the peak-memory
    high-water mark.

    With a :class:`~repro.core.partition.ParamPartition`, the returned
    function is ``local_update(trainable, batch, frozen) ->
    (local_trainable, mean_loss)``: the loss sees the merged full model,
    but gradients, optimizer state, and the returned local model cover the
    trainable sub-pytree only — the frozen base is a closed-over constant
    of the round, exactly the adapter fine-tuning contract.
    """
    if partition is not None:
        def local_update_part(trainable: Pytree, batch: dict,
                              frozen: Pytree):
            ostate0 = opt.init(trainable)

            def step(carry, _):
                train, ostate = carry
                loss, grads = jax.value_and_grad(
                    lambda tr: loss_fn(partition.merge(tr, frozen),
                                       batch))(train)
                train, ostate = opt.update(grads, ostate, train)
                return (train, ostate), loss

            if remat:
                step = jax.checkpoint(step)
            (train, _), losses = jax.lax.scan(
                step, (trainable, ostate0), None, length=local_steps)
            return train, losses.mean()

        return local_update_part

    def local_update(global_params: Pytree, batch: dict):
        ostate0 = opt.init(global_params)

        def step(carry, _):
            params, ostate = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            params, ostate = opt.update(grads, ostate, params)
            return (params, ostate), loss

        if remat:
            step = jax.checkpoint(step)
        (params, _), losses = jax.lax.scan(
            step, (global_params, ostate0), None, length=local_steps)
        return params, losses.mean()

    return local_update


def plain_sgd_client(loss_fn: LossFn, lr: float, local_steps: int = 1):
    """The paper's exact ClientUpdate: Θ_k ← Θ − η∇F_k(Θ)."""
    return make_local_update(loss_fn, sgd(lr), local_steps)
