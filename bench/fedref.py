"""Plain reference of federated rounds with layer divergence feedback.

Written from the paper (arXiv:2404.08324, Algorithm 1 and Eqs. 3-5) and
from the protocol the benchmark's traffic files state. It imports nothing
of the program under test and takes nothing the program made: the
weights, the dataset and the partition are the benchmark's own, made from
the seed.

One round ``t``:

1. key schedule: ``k = fold_in(PRNGKey(seed32), t)``, split into a
   client key, a batch key and an algorithm key;
2. K distinct participants, drawn uniformly without replacement;
3. each participant draws ``batch`` sample positions uniformly with
   replacement from its own shard;
4. local training: ``local_steps`` SGD steps at ``lr`` on that batch
   (the loss reported for the round is the participants' mean loss at
   the global model);
5. Eq. 3: per layer unit, the L2 norm of (local - global);
6. Eq. 4: per unit, the ``top_n`` participants with the largest
   divergence (exact ties to the lower position; for a near tie see
   ``FedCell.reference_record``);
7. Eq. 5: per unit, the mean of the selected participants' uploads,
   weighted by their shard sizes.

With 8-bit compression and error feedback (``quant_bits``), a
participant uploads, per unit, ``round(clip((delta + e) / s))`` with the
symmetric scale ``s = max|delta + e| / 127`` over the unit; the server
rebuilds ``global + levels * s``; the participant's residual ``e``
becomes ``delta + e - levels * s`` where it uploaded and stays where it
did not.

A layer unit is a top-level key of the trainable tree; a key in
``STACKED`` holds one unit per index of its leading axis.

The uplink count is the reference's own: per round, the selected units'
wire bytes (fp32/bf16 leaves at their item size, or ``params + 5`` bytes
per unit when quantized to 8 bits: int8 levels, an f32 scale and a width
byte) plus ``K * U`` f32 divergence scalars fed back to the server.
"""
from __future__ import annotations

import numpy as np

STACKED = ("blocks",)
DIV_SCALAR_BYTES = 4
QUANT_UNIT_HEADER_BYTES = 5


def program_seed(seed: int) -> int:
    """The 32-bit seed both sides key their round schedule with
    (``PRNGKey`` keeps the low 32 bits of an integer seed)."""
    return int(seed) % (2 ** 32)


# ----------------------------------------------------------------------
# layer units
# ----------------------------------------------------------------------
def leaf_items(tree, prefix=""):
    """("a/b/c", leaf) pairs of a nested dict, in sorted-key order."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from leaf_items(tree[k], f"{prefix}{k}/")
    else:
        yield prefix.rstrip("/"), tree


def unit_layout(tree) -> list[tuple[str, int]]:
    """[(top-level key, units it holds)] in unit order."""
    out = []
    for key in sorted(tree):
        leaves = [l for _, l in leaf_items(tree[key])]
        if not leaves:
            continue
        out.append((key, leaves[0].shape[0] if key in STACKED else 1))
    return out


def unit_names(tree) -> list[str]:
    """``"<top-level key>/<index>"`` of each unit, in unit order."""
    return [f"{key}/{i}" for key, n in unit_layout(tree) for i in range(n)]


def unit_params(tree) -> list[int]:
    """Parameters per unit, in unit order."""
    out = []
    for key, n in unit_layout(tree):
        per = sum(int(np.prod(l.shape)) for _, l in leaf_items(tree[key]))
        out.extend([per // n] * n)
    return out


def unit_bytes(tree) -> list[int]:
    out = []
    for key, n in unit_layout(tree):
        per = sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                  for _, l in leaf_items(tree[key]))
        out.extend([per // n] * n)
    return out


def _rows(leaf, n):
    return leaf.reshape(n, -1)


def unit_sqnorms(tree, jnp):
    """(U,) per-unit sums of squares of a (difference) tree, f32."""
    parts = []
    for key, n in unit_layout(tree):
        acc = 0.0
        for _, l in leaf_items(tree[key]):
            r = _rows(l.astype(jnp.float32), n)
            acc = acc + jnp.sum(r * r, axis=1)
        parts.append(acc)
    return jnp.concatenate(parts)


def per_unit_leaf(tree, per_unit, fn, jnp):
    """Apply ``fn(leaf, unit_values_broadcastable)`` per leaf."""
    out = {}
    u0 = 0
    for key, n in unit_layout(tree):
        seg = per_unit[..., u0:u0 + n]
        u0 += n

        def apply(l, seg=seg, n=n):
            # seg: (..., n); leaf: (..., n, ...) stacked or (..., ...)
            lead = seg.ndim - 1
            trail = l.ndim - lead - (1 if n > 1 else 0)
            if n > 1:
                s = seg.reshape(seg.shape + (1,) * trail)
            else:
                s = seg[..., 0].reshape(seg.shape[:-1] + (1,) * trail)
            return fn(l, s)

        out[key] = _map(apply, tree[key])
    return out


def _map(fn, tree, *rest):
    if isinstance(tree, dict):
        return {k: _map(fn, tree[k], *(r[k] for r in rest)) for k in tree}
    return fn(tree, *rest)


def tree_map(fn, tree, *rest):
    return _map(fn, tree, *rest)


# ----------------------------------------------------------------------
# one round
# ----------------------------------------------------------------------
def round_draw(jax, seed32: int, t: int, num_clients: int, k: int,
               batch: int, part_idx, part_sizes):
    """Participants (K,) and their sample indices (K, batch)."""
    jnp = jax.numpy
    key = jax.random.fold_in(jax.random.PRNGKey(seed32), t)
    ck, bk, _ = jax.random.split(key, 3)
    clients = jax.random.choice(ck, num_clients, shape=(k,), replace=False)
    sizes = part_sizes[clients]
    j = jax.random.randint(bk, (k, batch), 0, sizes[:, None])
    return clients, part_idx[clients[:, None], j].astype(jnp.int32)


def aggregate(jax, glob, locals_, sizes, top_n: int, quant_bits: int = 0,
              res_rows=None, swap=None):
    """Eqs. 3-5 over stacked (K, ...) locals.

    Returns ``(new_global, new_residual_rows, selection (K, U),
    margins (U,))`` where a unit's margin is the relative gap between its
    n-th and (n+1)-th largest divergence: how near the round's Eq. 4
    selection came to a tie there (1 when all K clients are selected).
    ``swap`` (U,) bool selects, in the units where it is set, the
    (n+1)-th client in place of the n-th: the other side of a near tie.
    """
    jnp = jax.numpy
    delta = tree_map(lambda l, g: l.astype(jnp.float32)
                     - g.astype(jnp.float32)[None], locals_, glob)
    divs = jnp.sqrt(jax.vmap(lambda d: unit_sqnorms(d, jnp))(delta))
    k = divs.shape[0]
    ranked, order = jax.lax.top_k(divs.T, min(top_n + 1, k))  # (U, n+1)
    top = order[:, :top_n]                                     # (U, n)
    if top_n < k:
        margins = ((ranked[:, top_n - 1] - ranked[:, top_n])
                   / ranked[:, top_n - 1])
        if swap is not None:
            top = top.at[:, top_n - 1].set(jnp.where(
                swap, order[:, top_n], order[:, top_n - 1]))
    else:
        margins = jnp.ones(divs.shape[1], jnp.float32)
    sel = jax.nn.one_hot(top, k, dtype=jnp.float32).sum(axis=1).T  # (K, U)
    w = sel * sizes.astype(jnp.float32)[:, None]
    frac = w / w.sum(axis=0)[None, :]
    new_res = None
    if quant_bits:
        qmax = 2.0 ** (quant_bits - 1) - 1.0
        v = tree_map(lambda d, e: d + e.astype(jnp.float32), delta, res_rows)
        maxabs = jnp.sqrt(jax.vmap(lambda x: _unit_maxsq(x, jnp))(v))
        scale = jnp.maximum(maxabs, 1e-12) / qmax                  # (K, U)
        levels = per_unit_leaf(
            v, scale, lambda l, s: jnp.round(jnp.clip(l / s, -qmax, qmax)),
            jnp)
        upload = per_unit_leaf(levels, scale, lambda l, s: l * s, jnp)
        new_res = _ef_update(jax, v, upload, res_rows, sel)
    else:
        upload = delta
    step = per_unit_leaf(upload, frac,
                         lambda l, f: jnp.sum(l * f, axis=0), jnp)
    new = tree_map(lambda g, s: (g.astype(jnp.float32) + s).astype(g.dtype),
                   glob, step)
    return new, new_res, sel, margins


def pick_units(base, alt, pick, jnp):
    """Per unit, ``alt``'s rows where ``pick`` ((U,) or (K, U)) is set and
    ``base``'s elsewhere."""
    masks = per_unit_leaf(base, jnp.asarray(pick, jnp.float32),
                          lambda l, s: jnp.broadcast_to(s, l.shape), jnp)
    return tree_map(lambda m, b, a: jnp.where(m > 0, a, b), masks, base, alt)


def unit_distances(a, b, jnp):
    """(U,) per-unit distances between two trees of the same layout."""
    return jnp.sqrt(unit_sqnorms(tree_map(
        lambda x, y: jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32),
        a, b), jnp))


def _unit_maxsq(tree, jnp):
    parts = []
    for key, n in unit_layout(tree):
        acc = 0.0
        for _, l in leaf_items(tree[key]):
            r = _rows(l, n)
            acc = jnp.maximum(acc, jnp.max(r * r, axis=1))
        parts.append(acc)
    return jnp.concatenate(parts)


def _ef_update(jax, v, recon, res_rows, sel):
    jnp = jax.numpy
    err = tree_map(lambda a, b: a - b, v, recon)
    gated = per_unit_leaf(err, sel, lambda l, g: l * g, jnp)
    kept = per_unit_leaf(res_rows, 1.0 - sel,
                         lambda l, g: l.astype(jnp.float32) * g, jnp)
    return tree_map(lambda a, b, r: (a + b).astype(r.dtype), gated, kept,
                    res_rows)


def uplink_bytes(tree, k: int, top_n: int, quant_bits: int = 0) -> float:
    """The reference's own wire bytes for one round (see module doc)."""
    if quant_bits:
        per_unit = [np.ceil(p * quant_bits / 8) + QUANT_UNIT_HEADER_BYTES
                    for p in unit_params(tree)]
    else:
        per_unit = unit_bytes(tree)
    units = len(per_unit)
    return float(top_n * sum(per_unit) + k * units * DIV_SCALAR_BYTES)
