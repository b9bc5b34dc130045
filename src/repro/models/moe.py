"""Dropless Mixture-of-Experts layer: DeepSeekMoE (shared + fine-grained
routed top-k, arXiv:2401.06066 / 2405.04434) and llama4-style top-1.

Every (token, choice) row is routed; none is dropped. The rows are sorted
by expert, and each expert's SwiGLU runs on its contiguous group of rows
as one grouped matrix product (:func:`grouped_matmul`), so the compiled
FLOPs are those of the *active* parameters (top-k), whatever the routing
skew. Per-expert LoRA factors ``a: (E, d_in, r)``, ``b: (E, r, d_out)``
(see ``models/lora.py``) run per group the same way.

Router: ``p = softmax(h W_g)`` in float32, top-k, renormalised when
``norm_topk_prob``, times ``routed_scaling_factor``. The layer returns
the Switch-style load-balance term (``lm_loss`` weighs it by
``aux_loss_coef``) and the tokens routed to each expert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig, dtype_of
from repro.models.layers import init_dense, init_mlp


def init_moe(key, cfg: ModelConfig):
    dt = dtype_of(cfg.param_dtype)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": init_dense(ks[0], d, e, jnp.float32),  # routing in fp32
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) / d**0.5).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) / d**0.5).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) / f**0.5).astype(dt),
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = init_mlp(ks[4], cfg,
                               d_ff=cfg.num_shared_experts * cfg.moe_d_ff)
    return p


#: megablox row tile; rows are padded to a multiple of it
GMM_ROWS = 512


def _gmm_tiling(m: int, k: int, n: int) -> tuple:
    """megablox tiles for one product; its backward products ask again
    with their own shapes (a LoRA factor's 16 is a whole dimension)."""
    return GMM_ROWS, min(k, 1024), min(n, 1024)


def _megablox(x, w, group_sizes, *, interpret: bool):
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m = x.shape[0]
    pad = -m % GMM_ROWS
    if pad:     # zero rows, counted in the last group, add nothing
        x = jnp.pad(x, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    out = gmm(x, w, group_sizes, x.dtype, _gmm_tiling, interpret=interpret)
    return out[:m] if pad else out


def _ragged_dot(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)


#: the grouped-matmul kernels: megablox ``gmm`` (its backward ``gmm`` and
#: ``tgmm``), the same kernels run by the Pallas interpreter (for tests
#: off the chip), and ``jax.lax.ragged_dot``
KERNELS = {
    "megablox": functools.partial(_megablox, interpret=False),
    "megablox_interpret": functools.partial(_megablox, interpret=True),
    "ragged_dot": _ragged_dot,
}


def _one_client_at_a_time(fn):
    """``fn`` with a batching rule: under ``jax.vmap`` it runs once per
    entry of the mapped axis (``lax.map``), each call unbatched. Neither
    kernel batches rows against weights shared by the batch."""
    fn_v = jax.custom_batching.custom_vmap(fn)

    @fn_v.def_vmap
    def _batched(axis_size, in_batched, *args):
        def one(mapped):
            it = iter(mapped)
            return fn_v(*[next(it) if b else a
                          for a, b in zip(args, in_batched)])
        out = jax.lax.map(one, tuple(a for a, b in zip(args, in_batched)
                                     if b))
        return out, jax.tree.map(lambda _: True, out)

    return fn_v


@functools.lru_cache(maxsize=None)
def _grouped(kernel: str):
    """The differentiable grouped product of one kernel: the forward and
    the backward (the kernel's own VJP) each carry the batching rule."""
    raw = KERNELS[kernel]
    fwd = _one_client_at_a_time(raw)
    bwd = _one_client_at_a_time(
        lambda x, w, gs, g: jax.vjp(lambda x, w: raw(x, w, gs), x, w)[1](g))

    @jax.custom_vjp
    def product(x, w, group_sizes):
        return fwd(x, w, group_sizes)

    def product_fwd(x, w, group_sizes):
        return fwd(x, w, group_sizes), (x, w, group_sizes)

    def product_bwd(res, g):
        x, w, group_sizes = res
        dx, dw = bwd(x, w, group_sizes, g)
        return dx, dw, np.zeros(group_sizes.shape, jax.dtypes.float0)

    product.defvjp(product_fwd, product_bwd)
    return product


def grouped_matmul(x, w, group_sizes, kernel: str | None = None):
    """Rows ``x (M, K)`` sorted into contiguous groups, ``group_sizes (E,)``
    rows each, times that group's ``w[e] (K, N)``: ``(M, N)`` in x.dtype,
    accumulated in float32.

    ``kernel`` names one of :data:`KERNELS`; by default the megablox
    kernel on a TPU, which ran the expert SwiGLU's forward and backward
    faster there than ``jax.lax.ragged_dot`` (PERF.md §6), and
    ``ragged_dot`` elsewhere. The result is in x.dtype so that the
    backward products take bfloat16 cotangents. Under ``jax.vmap`` (the
    stacked clients of a round) the product runs once per client."""
    if kernel is None:
        kernel = "megablox" if jax.default_backend() == "tpu" else "ragged_dot"
    return _grouped(kernel)(x, w.astype(x.dtype), group_sizes)


def _proj(x, p, name, group_sizes=None):
    """``x @ w`` plus the LoRA delta ``(x @ a) @ b``; per group of rows
    for the routed experts (``group_sizes``), one product for the shared
    ones. With ``b = 0`` the sum is the base product bit for bit."""
    def mm(a, w):
        if group_sizes is not None:
            return grouped_matmul(a, w, group_sizes)
        return jnp.einsum("td,df->tf", a, w.astype(a.dtype),
                          preferred_element_type=jnp.float32).astype(a.dtype)

    y = mm(x, p[name])
    ad = p.get("lora", {}).get(name)
    if ad is not None:
        y = y + mm(mm(x, ad["a"]), ad["b"])
    return y


def _swiglu(x, p, group_sizes=None):
    g = _proj(x, p, "w_gate", group_sizes)
    u = _proj(x, p, "w_up", group_sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return _proj(h, p, "w_down", group_sizes)


def route(p, xt, cfg: ModelConfig):
    """(weights (T, k) f32, experts (T, k) int32, probs (T, E) f32)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, eidx = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        w = w * cfg.routed_scaling_factor
    return w, eidx.astype(jnp.int32), probs


def moe_fwd(p, x: jnp.ndarray, cfg: ModelConfig):
    """x: (B, S, D) -> (out (B, S, D), aux, tokens per expert (E,) f32)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.moe_top_k
    xt = x.reshape(t, d)

    with jax.named_scope("moe.route"):
        w, eidx, probs = route(p, xt, cfg)
        flat = eidx.reshape(-1)                                 # (T*k,)
        order = jnp.argsort(flat, stable=True)                  # by expert
        group_sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        rows = xt[order // k]                                   # (T*k, D)

    with jax.named_scope("moe.experts"):
        out_rows = _swiglu(rows, p, group_sizes)                # (T*k, D)

    with jax.named_scope("moe.combine"):
        unsorted = out_rows[jnp.argsort(order)].reshape(t, k, d)
        out = jnp.einsum("tkd,tk->td", unsorted.astype(jnp.float32), w)
        out = out.astype(x.dtype)
        if cfg.num_shared_experts > 0:
            out = out + _swiglu(xt, p["shared"])

    # Switch-style load-balance term
    tokens = group_sizes.astype(jnp.float32)
    aux = e * jnp.sum(probs.mean(axis=0) * tokens / (t * k))
    return out.reshape(b, s, d), aux, tokens
