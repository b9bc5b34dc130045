"""The system under test, driven as a user drives it.

Each call of :meth:`FedRun.step` is one call of
``repro.federated.run_training_scan``: one compiled block of
``rounds_per_call`` rounds with no eval, continuing the same run through
``start_round`` and ``server_state`` as a user who checkpoints between
calls does. The first calls of a run go through the same method, so the
rounds that the correctness check compares are rounds of the timed path.

``fault`` plants one of the faults the check must catch, for the
benchmark's own tests and for reading the check's limits on the chip:

- ``state_unchanged``: every call hands back the model it was given;
- ``half_batch``: the loss sees the first half of each client batch
  (planted by the model module, which owns the loss);
- ``answer_altered``: the update a call makes to the subtree at
  ``alter_key`` (a ``/``-separated path of trainable keys) comes back
  doubled.
"""
from __future__ import annotations

import numpy as np

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


class FedRun:
    def __init__(self, loss_fn, params0, shards, flcfg, *, seed32: int,
                 rounds_per_call: int, fault: str | None = None,
                 alter_key: str | None = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
        self.loss_fn = loss_fn
        self.params = params0
        self.shards = shards
        self.fl = flcfg
        self.seed32 = seed32
        self.rounds_per_call = rounds_per_call
        self.fault = fault
        self.alter_key = alter_key
        self.state = None
        self.t = 0
        self.losses: list[float] = []
        self.uplink: list[float] = []

    def step(self) -> None:
        from repro.federated import run_training_scan
        r = self.rounds_per_call
        params, log = run_training_scan(
            self.params, self.loss_fn, self.shards, self.fl, rounds=r,
            seed=self.seed32, start_round=self.t,
            server_state=self.state)
        if self.fault == "state_unchanged":
            params, state = self.params, self.state
        else:
            state = log.final_state
            if self.fault == "answer_altered":
                params = _double_update(params, self.params, self.alter_key)
        self.losses.extend(float(x) for x in log.losses)
        cum = np.asarray(log.uplink_mb, np.float64) * 1e6
        self.uplink.extend(np.diff(np.concatenate(([0.0], cum))).tolist())
        self.params, self.state = params, state
        self.t += r

    def sync(self) -> None:
        import jax
        jax.block_until_ready(self.params)


def _double_update(new, old, path):
    import jax
    if not path:
        return jax.tree.map(lambda n, o: o + 2 * (n - o), new, old)
    key, _, rest = path.partition("/")
    out = dict(new)
    out[key] = _double_update(new[key], old[key], rest)
    return out
