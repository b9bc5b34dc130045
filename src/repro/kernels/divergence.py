"""Pallas TPU kernel: per-unit sum of squared differences (Eq. 3 inner loop).

Layer divergence in FedLDF reduces K × (full model size) elements per round:
for client ``k`` and layer-unit row ``r`` of a leaf,
``out[k, r] = Σ (local[k, r, …] − global[r, …])²`` in float32.

One call scores one leaf for all K clients. The kernel reads every local
element once and the global leaf once: the grid is ``(R, M-blocks,
K-blocks)`` with the clients innermost, so the global block's index is the
same across consecutive client steps and the pipeline does not fetch it
again, and the ``(K, 1, 128)`` output block of row ``r`` stays in VMEM
across the whole reduction (TPU grids run sequentially, minor-most last).
Each step adds its block's sum over sublanes, folded to 128 lanes, into its
clients' output rows; the wrapper sums the 128 lanes.

Each leaf is viewed as ``(R, M, N)`` from its own shape (:func:`leaf_view`):

- **in place** where the unit's minor dimension ``N`` is a multiple of 128
  and its second-minor a multiple of the dtype's sublane tile (8 for f32,
  16 for bf16): merging the leading dimensions is a bitcast in the TPU's
  tiled layout, so no copy is made. A conv weight ``(3, 3, Cin, Cout)`` is
  ``(1, 9·Cin, Cout)``; a stacked LoRA B ``(L, 16, d_out)`` is itself.
- **folded** otherwise: the unit row is flattened into 128-lane sub-rows,
  ``(R, ⌈P/128⌉, 128)``, zero-padding only the tail of each row
  (``(0 − 0)² = 0``, so the result is exact). Biases, scales, ``fc.w``
  ``(2048, 10)`` and LoRA A ``(L, d_in, 16)`` take this view.

No leaf is padded by rows. Blocks are sized to ``block_bytes`` (2 MiB by
default) per operand: a unit that fits whole is one block per step and
several clients share a step; a larger unit is cut into balanced blocks
of sublane-aligned rows, and the ragged rows of the last block are masked
in the kernel. Two operands double-buffered stay within the default scoped
VMEM.

``interpret=None`` resolves via the backend check in ``kernels/ops``
(compiled on TPU, interpret elsewhere); ``kernels/ref.py`` holds the
pure-jnp oracle that doubles as the CPU fast path.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_BYTES = 2 << 20


def _sublanes(itemsize: int) -> int:
    """Rows of one (sublane, 128) tile: 8 for f32, 16 for bf16."""
    return 32 // itemsize


@dataclasses.dataclass(frozen=True)
class LeafView:
    """How one leaf is read: ``rows`` unit rows of ``(m, n)`` elements."""
    rows: int
    m: int
    n: int
    in_place: bool
    itemsize: int

    @property
    def nbytes(self) -> int:
        """Bytes of one client's leaf as the kernel reads it (tail pad
        included)."""
        return self.rows * self.m * self.n * self.itemsize


def leaf_view(shape, dtype, rows: int = 1,
              block_bytes: int = DEFAULT_BLOCK_BYTES) -> LeafView:
    """The kernel's view of a leaf of ``shape`` holding ``rows`` unit rows
    (``shape[0] == rows`` when ``rows > 1``)."""
    unit = tuple(shape[1:]) if rows > 1 else tuple(shape)
    item = np.dtype(dtype).itemsize
    sub = _sublanes(item)
    if (len(unit) >= 2 and unit[-1] % LANES == 0 and unit[-2] % sub == 0
            and sub * unit[-1] * item <= block_bytes):
        return LeafView(rows, int(np.prod(unit[:-1])), unit[-1], True, item)
    size = int(np.prod(unit))
    return LeafView(rows, max(1, pl.cdiv(size, LANES)), LANES, False, item)


def _blocks(view: LeafView, k: int, block_bytes: int):
    """(clients per block, rows per block, row blocks)."""
    row_bytes = view.n * view.itemsize
    sub = _sublanes(view.itemsize)
    bm_max = max(sub, block_bytes // row_bytes // sub * sub)
    if view.m <= bm_max:
        fit = max(1, block_bytes // (view.m * row_bytes))
        kb = max(d for d in range(1, min(k, fit) + 1) if k % d == 0)
        return kb, view.m, 1
    nm = pl.cdiv(view.m, bm_max)
    bm = pl.cdiv(pl.cdiv(view.m, nm), sub) * sub
    return 1, bm, pl.cdiv(view.m, bm)


def _sqdiff_kernel(a_ref, b_ref, out_ref, *, kb, bm, m, n):
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((i == 0) & (k == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = a_ref[...].astype(jnp.float32) - b_ref[...].astype(jnp.float32)
    if m % bm:   # ragged last block: its rows past m hold no data
        row = i * bm + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
        d = jnp.where(row < m, d, 0.0)
    s = jnp.sum(d * d, axis=1, keepdims=True)          # (kb, 1, n)
    lanes = s[..., :LANES]
    for j in range(1, n // LANES):
        lanes = lanes + s[..., j * LANES:(j + 1) * LANES]
    out_ref[pl.ds(k * kb, kb)] += lanes


def _sqdiff_call(a, b, view: LeafView, block_bytes: int, interpret: bool):
    """(K, R, M, N) locals, (R, M, N) global -> (K, R) float32."""
    k = a.shape[0]
    kb, bm, nm = _blocks(view, k, block_bytes)
    kernel = functools.partial(_sqdiff_kernel, kb=kb, bm=bm, m=view.m,
                               n=view.n)
    ops = (a, b)
    if not interpret:
        # Left to itself, XLA may place an operand small enough in VMEM
        # (a fusion writing it there, or an async copy ahead of the call),
        # doing the kernel's HBM reads outside it; keep both in HBM.
        ops = tuple(pltpu.with_memory_space_constraint(x, pltpu.HBM)
                    for x in ops)
    out = pl.pallas_call(
        kernel,
        grid=(view.rows, nm, k // kb),
        in_specs=[
            pl.BlockSpec((kb, pl.squeezed, bm, view.n),
                         lambda r, i, c: (c, r, i, 0)),
            pl.BlockSpec((pl.squeezed, bm, view.n),
                         lambda r, i, c: (r, i, 0)),
        ],
        out_specs=pl.BlockSpec((pl.squeezed, k, 1, LANES),
                               lambda r, i, c: (r, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((view.rows, k, 1, LANES),
                                       jnp.float32),
        name="sqdiff_rowsum",
        interpret=interpret,
    )(*ops)
    return jnp.sum(out, axis=(2, 3)).T


def _as_view(x, lead: tuple, view: LeafView):
    """Reshape ``x`` (``lead + leaf shape``) to ``lead + (R, M, N)``: a
    bitcast in place, a flatten and tail pad when folded."""
    if view.in_place:
        return x.reshape(lead + (view.rows, view.m, view.n))
    x = x.reshape(lead + (view.rows, -1))
    pad = view.m * view.n - x.shape[-1]
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(lead + (view.rows, view.m, view.n))


@functools.lru_cache(maxsize=None)
def _units_fn(rows: int, block_bytes: int, interpret: bool):
    """The K-client call for one static setting, with a batching rule: under
    ``jax.vmap`` the mapped axis joins the clients, so a vmapped caller
    (``jax.vmap(umap.divergence)``) still makes one call per leaf. The
    HBM constraint in :func:`_sqdiff_call` has no batching rule of its own."""

    @jax.custom_batching.custom_vmap
    def units(a, b):
        view = leaf_view(b.shape, b.dtype, rows, block_bytes)
        return _sqdiff_call(_as_view(a, a.shape[:1], view),
                            _as_view(b, (), view), view, block_bytes,
                            interpret)

    @units.def_vmap
    def _batched(axis_size, in_batched, a, b):
        a_batched, b_batched = in_batched
        if not a_batched:
            a = jnp.broadcast_to(a, (axis_size,) + a.shape)
        if b_batched:   # a global per batch entry: one call for each
            return jax.lax.map(lambda ab: units(*ab), (a, b)), True
        k = a.shape[1]
        out = units(a.reshape((axis_size * k,) + a.shape[2:]), b)
        return out.reshape(axis_size, k, rows), True

    return units


@functools.partial(jax.jit, static_argnames=("rows", "block_bytes",
                                             "interpret"))
def sqdiff_units(a: jnp.ndarray, b: jnp.ndarray, *, rows: int = 1,
                 block_bytes: int = DEFAULT_BLOCK_BYTES,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Eq. 3 for one leaf and K clients: ``a`` is ``(K,) + b.shape``, the
    leaf holds ``rows`` unit rows (leading dimension when ``rows > 1``).
    Returns ``(K, rows)`` float32 per-unit Σ(a−b)²."""
    if interpret is None:
        from repro.kernels import ops
        interpret = ops._interpret()
    assert a.shape[1:] == b.shape and a.dtype == b.dtype
    return _units_fn(rows, block_bytes, interpret)(a, b)


def sqdiff_rowsum(a: jnp.ndarray, b: jnp.ndarray, *,
                  block_bytes: int = DEFAULT_BLOCK_BYTES,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Per-row Σ(a−b)² via Pallas. a, b: (R, C) → (R,) float32: one client
    of :func:`sqdiff_units` with each row a unit."""
    assert a.shape == b.shape and a.ndim == 2
    return sqdiff_units(a[None], b, rows=a.shape[0],
                        block_bytes=block_bytes, interpret=interpret)[0]
