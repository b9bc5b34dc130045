"""What every federated cell shares: the program's run, its first three
calls as the correctness check's steps, and the reference's three.

A model module under ``bench/models/`` subclasses :class:`FedCell` and
supplies the dataset, the weights, the program's loss, the reference's
local update and the cost functions. The harness (``bench/run.py``)
drives the rest:

    cell = Workload(config, traffic, seed)
    cell.build()          # data, weights, the program's run objects
    cell.first_steps()    # the first three calls: compile, then check
    cell.step() ...       # the measured window
    cell.release()        # free the program's state
    cell.reference_record()
"""
from __future__ import annotations

import time

from bench import check, fedref
from bench.fedprog import FedRun

CHECK_STEPS = 3


class FedCell:
    #: path of the trainable subtree whose update the ``answer_altered``
    #: fault doubles
    alter_key: str = ""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 fault: str | None = None):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seed32 = fedref.program_seed(seed)
        self.fault = fault
        self.rounds_per_call = int(traffic["rounds_per_call"])
        self.timings: dict = {}
        self.prog: dict = {}

    # ---- model-specific -------------------------------------------------
    def make_data(self) -> None:
        """Sets ``self.xs``, ``self.ys``, ``self.parts`` (list of index
        arrays), ``self.part_idx`` (N, S) and ``self.part_sizes`` (N,)."""
        raise NotImplementedError

    def make_weights(self):
        """Returns the full parameter tree on the device."""
        raise NotImplementedError

    def program_loss(self):
        raise NotImplementedError

    def partition(self, params):
        return None

    def split(self, params):
        """(trainable, frozen) trees; frozen is {} when all train."""
        return params, {}

    def reference_locals(self, trainable, frozen, idx, control: bool):
        """Stacked (K, ...) local trainable trees and the mean loss."""
        raise NotImplementedError

    def costs(self) -> dict:
        raise NotImplementedError

    # ---- shared ---------------------------------------------------------
    def flconfig(self, params):
        from repro.core.wire import CompressionConfig
        from repro.federated import FLConfig
        tr = self.traffic
        comp = None
        if tr.get("compression"):
            c = tr["compression"]
            comp = CompressionConfig(bits=int(c["bits"]),
                                     error_feedback=bool(c["error_feedback"]))
        return FLConfig(algo=tr["algo"], num_clients=tr["num_clients"],
                        clients_per_round=tr["clients_per_round"],
                        top_n=tr["top_n"], local_steps=tr["local_steps"],
                        lr=tr["lr"], mode=tr["mode"],
                        batch_per_client=tr["batch_per_client"],
                        compression=comp, partition=self.partition(params))

    @property
    def quant_bits(self) -> int:
        c = self.traffic.get("compression")
        return int(c["bits"]) if c else 0

    @property
    def error_feedback(self) -> bool:
        c = self.traffic.get("compression")
        return bool(c and c["error_feedback"])

    def build(self) -> None:
        import jax
        from repro.data import ClientShards, FederatedData
        # the precision the configuration states for the program's
        # matmuls and convolutions ("default" leaves JAX's own)
        prec = self.config.get("matmul_precision", "default")
        jax.config.update("jax_default_matmul_precision",
                          None if prec == "default" else prec)
        t = time.perf_counter()
        self.make_data()
        jax.block_until_ready((self.xs, self.ys))
        self.timings["data_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.params0 = self.make_weights()
        jax.block_until_ready(self.params0)
        self.timings["weights_s"] = time.perf_counter() - t
        t = time.perf_counter()
        x_key, y_key = self.data_keys
        shards = ClientShards.from_federated(FederatedData(
            self.xs, self.ys, self.parts, x_key=x_key, y_key=y_key))
        self.run = FedRun(self.program_loss(), self.params0, shards,
                          self.flconfig(self.params0), seed32=self.seed32,
                          rounds_per_call=self.rounds_per_call,
                          fault=self.fault, alter_key=self.alter_key)
        self.timings["program_objects_s"] = time.perf_counter() - t

    def first_steps(self) -> None:
        """The first CHECK_STEPS calls, through the window's own call.

        The first compiles (or loads from the compile cache); the check
        reads the model after the first and after the last of them, and
        keeps a host copy of the trainable model at both points
        (``prog["observed"]``, by rounds done) for the reference to tell
        which side of a near tie the program took there.
        """
        import jax
        tr0, fz0 = self.split(self.params0)
        observed = {}
        t = time.perf_counter()
        for i in range(CHECK_STEPS):
            self.run.step()
            self.run.sync()
            if i == 0:
                self.timings["first_call_s"] = time.perf_counter() - t
                tr1, _ = self.split(self.run.params)
                self.prog["delta1"] = check.change_norms(jax, tr1, tr0)
                observed[self.rounds_per_call] = jax.device_get(tr1)
                del tr1
        self.timings["check_calls_s"] = time.perf_counter() - t
        tr3, fz3 = self.split(self.run.params)
        self.prog["delta3"] = check.change_norms(jax, tr3, tr0)
        observed[CHECK_STEPS * self.rounds_per_call] = jax.device_get(tr3)
        self.prog["observed"] = observed
        if fz0:
            self.prog["frozen_change"] = check.max_change(jax, fz3, fz0)
        if self.error_feedback:
            state = self.run.state
            self.prog["residual"] = (
                check.norms(jax, state["client"]["residual"], lead=1)
                if state is not None else
                {p: [0.0] * len(r)
                 for p, r in check.norms(jax, tr0).items()})
        n = CHECK_STEPS * self.rounds_per_call
        self.prog["loss"] = self.run.losses[:n]
        self.prog["uplink"] = self.run.uplink[:n]

    def step(self) -> None:
        self.run.step()

    def sync(self) -> None:
        self.run.sync()

    def release(self) -> None:
        """Free the program's model, state and shards."""
        import gc
        self.run.params = self.run.state = self.run.shards = None
        gc.collect()

    def reference_record(self, control: bool = False, observed=None,
                         tie_margin: float = 0.0) -> dict:
        """The plain reference's first CHECK_STEPS calls' worth of rounds.

        ``control`` computes local training one precision below the
        configuration's (see the model module), everything else as in
        the reference; its record keeps its own ``observed`` models, as
        the program's does.

        Near ties. Where a unit's Eq. 4 margin falls under ``tie_margin``,
        rounding alone decides which of the n-th and (n+1)-th clients the
        compared side selects there, and either is a right answer. In a
        round after which the compared side's model was observed
        (``observed``, by rounds done), the reference takes, unit by
        unit, the side whose aggregate lies nearer that model, and goes
        on from it (``resolved``). In any other round it cannot tell, and
        the unit is left out of the norms from that round on
        (``excluded``: unit -> round).
        """
        import jax
        import jax.numpy as jnp
        import numpy as np
        tr = self.traffic
        n_rounds = CHECK_STEPS * self.rounds_per_call
        tr0, fz0 = self.split(self.params0)
        p = tr0
        res = None
        if self.error_feedback:
            res = fedref.tree_map(
                lambda l: jnp.zeros((tr["num_clients"],) + l.shape, l.dtype),
                tr0)
        agg = jax.jit(
            lambda g, loc, sizes, rows, swap: fedref.aggregate(
                jax, g, loc, sizes, tr["top_n"], self.quant_bits, rows, swap))
        units = fedref.unit_names(tr0)
        rec = {"loss": [], "uplink": [], "margins": [], "units": units,
               "resolved": [], "excluded": {},
               "delta1_rounds": self.rounds_per_call, "observed": {}}
        per_round_bytes = fedref.uplink_bytes(
            tr0, tr["clients_per_round"], tr["top_n"], self.quant_bits)
        no_swap = jnp.zeros((len(units),), bool)
        for t in range(n_rounds):
            clients, idx = fedref.round_draw(
                jax, self.seed32, t, tr["num_clients"],
                tr["clients_per_round"], tr["batch_per_client"],
                self.part_idx, self.part_sizes)
            locals_, loss = self.reference_locals(p, fz0, idx, control)
            rows = (None if res is None
                    else fedref.tree_map(lambda s: s[clients], res))
            sizes = self.part_sizes[clients]
            new, new_rows, _, margins = agg(p, locals_, sizes, rows, no_swap)
            margins = np.asarray(margins, np.float64)
            tied = margins < tie_margin
            seen = (observed or {}).get(t + 1)
            if tied.any() and seen is not None:
                alt, alt_rows, _, _ = agg(p, locals_, sizes, rows,
                                          jnp.asarray(tied))
                pick = tied & np.asarray(
                    fedref.unit_distances(alt, seen, jnp)
                    < fedref.unit_distances(new, seen, jnp))
                new = fedref.pick_units(new, alt, pick, jnp)
                if res is not None:
                    new_rows = fedref.pick_units(
                        new_rows, alt_rows,
                        np.broadcast_to(pick, (len(clients), len(units))),
                        jnp)
                rec["resolved"].extend(
                    [t, units[u], bool(pick[u])] for u in np.flatnonzero(tied))
            elif tied.any():
                for u in np.flatnonzero(tied):
                    rec["excluded"].setdefault(units[u], t)
            p, rows = new, new_rows
            rec["margins"].append([float(m) for m in margins])
            del locals_
            if res is not None:
                res = fedref.tree_map(lambda s, r: s.at[clients].set(r),
                                      res, rows)
            rec["loss"].append(float(loss))
            rec["uplink"].append(per_round_bytes)
            if t + 1 == self.rounds_per_call:
                rec["delta1"] = check.change_norms(jax, p, tr0)
                rec["observed"][t + 1] = jax.device_get(p)
        rec["delta3"] = check.change_norms(jax, p, tr0)
        rec["observed"][n_rounds] = jax.device_get(p)
        if fz0:
            rec["frozen_change"] = 0.0     # the reference never moves it
        if res is not None:
            rec["residual"] = check.norms(jax, res, lead=1)
        return rec
