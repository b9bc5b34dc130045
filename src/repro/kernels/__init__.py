"""Pallas TPU kernels.

FedLDF hot spots:
- divergence.py : per-unit Σ(a−b)² of one leaf for K clients (Eq. 3),
                  each leaf read once in its own layout.
- aggregate.py  : fused acc += w[r]·x (Eq. 5 accumulation).
- uplink.py     : fused packed-uplink dequant + EF update + Eq. 5
                  accumulate over int8 wire buffers (core/wire.py).

Substrate hot spot (motivated by §Perf pairs A/E — XLA keeps flash
probabilities in HBM; the fused kernel keeps them in VMEM):
- flash_attention.py : GQA flash attention (causal/sliding-window).

- ref.py : pure-jnp oracles (ground truth + CPU fast path).
- ops.py : backend-dispatching wrappers used by repro.core.
"""
from repro.kernels import (aggregate, divergence, flash_attention, ops, ref,
                           uplink)

__all__ = ["aggregate", "divergence", "flash_attention", "ops", "ref",
           "uplink"]
