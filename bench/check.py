"""The comparison that decides ``correct``.

The program's first three calls and the plain reference's first three
rounds each give a record:

- ``loss``: the round loss of each round (``loss_first``: of the first
  round alone, before any selection or aggregation has run, so before
  the rounding of one round can be grown by the next);
- ``uplink``: the uplink bytes of each round;
- ``delta1``: per trainable leaf, the norm of the model's change over the
  first call, the update the server applies (the "first gradient");
- ``delta3``: per trainable leaf, the norm of the change over three
  calls;
- ``residual`` (error feedback only): per leaf, the norm of the
  clients' residual store after three calls;
- ``frozen_change`` (frozen base only): the largest change of any frozen
  leaf (0 in the reference, which never moves it); the limit is 0.

Norms are kept per layer unit (one row of a stacked leaf), so that a
unit can be left out. Each number is a gap between the program's reading
and the reference's, as a share of the reference's. A norm gives two:
``<key>``, taken by the worst leaf against the larger of that leaf's
reference norm and the median leaf's, so that a leaf whose change is all
but nought does not divide by nought; and ``<key>_median``, the median of
those leaf gaps, which one small leaf's rounding cannot move. Leaves
whose reference norm is under ``NOUGHT_SHARE`` of the median leaf's move
by rounding alone (a convolution bias ahead of a batch normalisation; a
LoRA ``a`` factor while its ``b`` is still zero) and are counted in
neither.

Near ties. Where a unit's Eq. 4 selection came within the cell's
``tie_margin`` of a tie (the relative gap between the n-th and the
(n+1)-th divergence), rounding alone decides which client the compared
side selects there. In a round after which the program's model was
observed, the reference follows the side the program took
(``FedCell.reference_record``); in any other round the unit is left out
of the norms from that round on, and the loss is compared up to and
including that round (a round's loss is taken at the model the earlier
rounds made). A norm that would then cover fewer than ``MIN_UNIT_SHARE``
of the units reads infinite, so it fails: a check that has lost half of
the model has not checked it. ``excluded_units`` and ``resolved_ties``
are readings, not limits.

A cell's limits file (``bench/limits/<cell>.json``) says which numbers
are compared, and its ``tie_margin`` (0 where absent); the other numbers
are printed as readings only.
"""
from __future__ import annotations

import json
import math
import os
import statistics

NOUGHT_SHARE = 1e-3
MIN_UNIT_SHARE = 0.5
NORM_KEYS = ("delta1", "delta3", "residual")


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """{leaf: gap of norms} over the leaves not left out as nought."""
    med = statistics.median(ref.values())
    return {n: (abs(prog[n] - r) / max(r, med) if math.isfinite(prog[n])
                else math.inf)
            for n, r in ref.items() if r >= NOUGHT_SHARE * med}


def leaf_values(rows: dict, skip=frozenset()) -> dict:
    """{leaf: norm over its unit rows}, leaving out the units in ``skip``
    (``"<top-level key>/<row>"``) and leaves with no row left."""
    out = {}
    for path, norms in rows.items():
        key = path.split("/")[0]
        kept = [x for i, x in enumerate(norms) if f"{key}/{i}" not in skip]
        if kept:
            out[path] = math.sqrt(sum(x * x for x in kept))
    return out


def excluded_units(ref: dict, rounds: int) -> set:
    """Units the reference left out at a near tie in one of its first
    ``rounds`` rounds."""
    return {u for u, t in ref["excluded"].items() if t < rounds}


def series_gap(prog: list, ref: list) -> float:
    worst = 0.0
    for p, r in zip(prog, ref, strict=True):
        if not math.isfinite(p):
            return math.inf
        worst = max(worst, abs(p - r) / abs(r))
    return worst


def norm_gaps(prog: dict, ref: dict, key: str) -> dict | None:
    """{leaf: gap} over the units kept, or None where fewer than
    MIN_UNIT_SHARE of the units are kept."""
    rounds = ref["delta1_rounds"] if key == "delta1" else len(ref["loss"])
    skip = excluded_units(ref, rounds)
    if len(ref["units"]) - len(skip) < MIN_UNIT_SHARE * len(ref["units"]):
        return None
    return leaf_gaps(leaf_values(prog[key], skip),
                     leaf_values(ref[key], skip))


def numbers(prog: dict, ref: dict) -> dict:
    """{name: gap} for every number the cell's records carry."""
    n = len(ref["loss"])
    upto = min(ref["excluded"].values(), default=n - 1) + 1
    out = {"loss": series_gap(prog["loss"][:upto], ref["loss"][:upto]),
           "loss_first": series_gap(prog["loss"][:1], ref["loss"][:1]),
           "uplink": series_gap(prog["uplink"], ref["uplink"]),
           "excluded_units": len(ref["excluded"]),
           "resolved_ties": len(ref["resolved"])}
    for key in NORM_KEYS:
        if key in ref:
            gaps = norm_gaps(prog, ref, key)
            if not gaps:
                out[key] = out[f"{key}_median"] = math.inf
                continue
            out[key] = max(gaps.values())
            out[f"{key}_median"] = statistics.median(gaps.values())
    if "frozen_change" in prog:
        out["frozen_change"] = prog["frozen_change"]
    return out


def worst_leaves(prog: dict, ref: dict) -> dict:
    """Each norm number's three worst leaves, for a look at the cause."""
    return {f"{key}_worst": sorted(
                (norm_gaps(prog, ref, key) or {}).items(),
                key=lambda x: -x[1])[:3]
            for key in NORM_KEYS if key in ref}


def _limits_file(bench_dir: str, workload: str) -> dict:
    with open(os.path.join(bench_dir, "limits", f"{workload}.json")) as f:
        return json.load(f)


def load_limits(bench_dir: str, workload: str) -> dict:
    return _limits_file(bench_dir, workload)["limits"]


def load_tie_margin(bench_dir: str, workload: str) -> float:
    return float(_limits_file(bench_dir, workload).get("tie_margin", 0.0))


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers that have a
    limit; a limit that names no number is an error of the benchmark."""
    missing = set(limits) - set(values)
    if missing:
        raise KeyError(f"limits {sorted(missing)} name no number; the "
                       f"numbers are {sorted(values)}")
    table = {n: {"value": values[n], "limit": lim}
             for n, lim in limits.items()}
    ok = all(math.isfinite(r["value"]) and r["value"] <= r["limit"]
             for r in table.values())
    return ok, table


# ----------------------------------------------------------------------
# device-side readings (one compiled program per tree structure)
# ----------------------------------------------------------------------
_JITTED: dict = {}


def _jit(name, fn, static=()):
    import jax
    if name not in _JITTED:
        _JITTED[name] = jax.jit(fn, static_argnums=static)
    return _JITTED[name]


def _row_norms(xs, stacked, lead):
    """Per leaf, the norm of each unit row (the axis ``lead`` of a stacked
    leaf) or of the whole leaf as one row."""
    import jax.numpy as jnp
    out = []
    for x, s in zip(xs, stacked):
        sq = jnp.square(x.astype(jnp.float32))
        axes = tuple(a for a in range(x.ndim) if a != lead) if s else None
        out.append(jnp.reshape(jnp.sqrt(jnp.sum(sq, axis=axes)), (-1,)))
    return out


def _diff_row_norms(xs, ys, stacked, lead):
    import jax.numpy as jnp
    return _row_norms([x.astype(jnp.float32) - y.astype(jnp.float32)
                       for x, y in zip(xs, ys)], stacked, lead)


def _max_abs_diff(xs, ys):
    import jax.numpy as jnp
    return jnp.max(jnp.stack([
        jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
        for x, y in zip(xs, ys)]))


def _stacked(paths) -> tuple:
    from bench.fedref import STACKED
    return tuple(p.split("/")[0] in STACKED for p in paths)


def change_norms(jax, new, old) -> dict:
    """{path: [||new - old|| per unit row]} per leaf."""
    from bench.fedref import leaf_items
    a, b = list(leaf_items(new)), list(leaf_items(old))
    paths = [p for p, _ in a]
    fn = _jit("diff_row_norms", _diff_row_norms, static=(2, 3))
    rows = fn([x for _, x in a], [y for _, y in b], _stacked(paths), 0)
    return {p: [float(v) for v in r]
            for p, r in zip(paths, jax.device_get(rows))}


def norms(jax, tree, lead: int = 0) -> dict:
    """{path: [norm per unit row]} per leaf; ``lead`` is the unit axis of
    a stacked leaf (1 for a per-client store)."""
    from bench.fedref import leaf_items
    items = list(leaf_items(tree))
    paths = [p for p, _ in items]
    fn = _jit("row_norms", _row_norms, static=(1, 2))
    rows = fn([x for _, x in items], _stacked(paths), lead)
    return {p: [float(v) for v in r]
            for p, r in zip(paths, jax.device_get(rows))}


def max_change(jax, new, old) -> float:
    """Largest elementwise change over all leaves (0 for no leaves)."""
    from bench.fedref import leaf_items
    a = [x for _, x in leaf_items(new)]
    b = [x for _, x in leaf_items(old)]
    if not a:
        return 0.0
    return float(_jit("max_abs_diff", _max_abs_diff)(a, b))
