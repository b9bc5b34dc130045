"""LoRA-style adapters over the transformer zoo [arXiv:2106.09685 idiom].

``inject_lora`` drops low-rank factor pairs ``{"a": (L, d_in, r),
"b": (L, r, d_out)}`` next to the stacked dense projections they adapt
(``blocks["attn"]["lora"]["wq"]``, ...). An expert tensor
``(L, E, d_in, d_out)`` gets one pair per expert, ``a: (L, E, d_in, r)``
and ``b: (L, E, r, d_out)``, which the MoE layer applies per group of
rows (``models/moe.py``). ``b`` is zero-initialised, so the
adapted forward equals the base forward bit-for-bit at injection time —
training moves only the factors. The forward hookup lives in
:func:`repro.models.layers.lora_dense`.

Combined with :class:`repro.core.partition.ParamPartition` (see
``lora_partition``) this is the adapter-only uplink workload: the frozen
base stays device-resident and is broadcast once, the wire carries factors
only, and FedLDF's per-layer divergence (Eq. 3) scores per-depth adapter
units — the stacked (L, ...) leading axis folds into the existing
``blocks/i`` units of :class:`repro.core.units.UnitMap`.

Adapted projections per block module (only those present are touched):

    attn:       wq wk wv wo        (dense / moe / hybrid / enc / dec families)
                wq wkv_a wkv_b wo  (multi-head latent attention)
    mlp:        w_gate w_up w_down (all non-moe FFN blocks)
    moe:        w_gate w_up w_down (every routed expert)
    moe/shared: w_gate w_up w_down (the shared experts' SwiGLU)
    ssm:        in_proj out_proj   (mamba2 / hybrid families)

Cross-attention and the router are not adapted.
"""
from __future__ import annotations

from typing import Any, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import ParamPartition

Pytree = Any

# module path -> projection names eligible for adapters (stacked
# (L, [E,] d_in, d_out) leaves only; missing modules/names are skipped).
LORA_TARGETS: Mapping[str, Tuple[str, ...]] = {
    "attn": ("wq", "wk", "wv", "wo", "wkv_a", "wkv_b"),
    "mlp": ("w_gate", "w_up", "w_down"),
    "moe": ("w_gate", "w_up", "w_down"),
    "moe/shared": ("w_gate", "w_up", "w_down"),
    "ssm": ("in_proj", "out_proj"),
}

# stacked-block subtrees adapters may live under (see transformer.init_params)
LORA_SUBTREES: Tuple[str, ...] = ("blocks", "enc_blocks", "dense")


def _adapt_module(key, mdict: dict, projs, rank: int):
    """(key, module dict with its ``lora`` entry, adapters injected)."""
    lora = dict(mdict.get("lora", {}))
    injected = 0
    for name in projs:
        w = mdict.get(name)
        if w is None or getattr(w, "ndim", 0) not in (3, 4):
            continue
        *lead, din, dout = w.shape
        r = min(rank, din, dout)
        key, ka = jax.random.split(key)
        a = (jax.random.normal(ka, (*lead, din, r))
             / np.sqrt(din)).astype(w.dtype)
        lora[name] = {"a": a, "b": jnp.zeros((*lead, r, dout), w.dtype)}
        injected += 1
    if lora:
        mdict = dict(mdict, lora=lora)
    return key, mdict, injected


def inject_lora(key, params: Pytree, rank: int,
                targets: Optional[Mapping[str, Tuple[str, ...]]] = None,
                subtrees: Tuple[str, ...] = LORA_SUBTREES) -> Pytree:
    """Returns a copy of ``params`` with adapter factors injected.

    ``rank`` is clipped per-projection to ``min(rank, d_in, d_out)``.
    ``a`` ~ N(0, 1/d_in), ``b`` = 0 (forward-exact at init). Raises
    ValueError if no eligible projection exists — an empty adapter set
    would make the trainable partition empty.
    """
    if rank < 1:
        raise ValueError(f"lora rank must be >= 1, got {rank}")
    targets = LORA_TARGETS if targets is None else targets
    out = dict(params)
    injected = 0
    for sub in subtrees:
        if sub not in params:
            continue
        blocks = dict(params[sub])
        # outer modules first, so a nested module ("moe/shared") lands in
        # its parent's updated dict
        for path in sorted(targets, key=lambda p: p.count("/")):
            *parents, mod = path.split("/")
            node = blocks
            for seg in parents:         # copy the parents, never mutate
                if not isinstance(node.get(seg), dict):
                    break
                node[seg] = dict(node[seg])
                node = node[seg]
            else:
                if mod not in node:
                    continue
                key, node[mod], n = _adapt_module(key, dict(node[mod]),
                                                  targets[path], rank)
                injected += n
        out[sub] = blocks
    if injected == 0:
        raise ValueError(
            "inject_lora found no eligible projection: params has none of "
            f"{sorted(targets)} with stacked (L, d_in, d_out) leaves under "
            f"{subtrees}")
    return out


def lora_partition(params: Pytree) -> ParamPartition:
    """Trainable = every leaf under a ``lora`` path segment; rest frozen.

    Pass the result as ``FLConfig(partition=...)`` to get the adapter-only
    uplink: the base model is broadcast once and never travels the wire.
    """
    return ParamPartition.by_substring(params, "lora")
