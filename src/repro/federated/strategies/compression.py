"""Quantize(+error-feedback) upload wrapper, as a composable strategy.

``FLConfig(compression=CompressionConfig(...))`` composes
:class:`QuantizedUpload` around the configured base strategy (see
:func:`repro.federated.strategies.make_strategy`): selection and
aggregation delegate to the inner strategy unchanged, while the per-client
payload is re-expressed as ``Ĝ + dequant(Q_b(Δ + e))`` with optional
client-side error feedback whose residuals advance only where a layer
actually shipped.

Two execution paths, chosen by ``CompressionConfig.fused``:

- **packed** (default): the stacked client deltas are quantized into a
  :class:`repro.core.wire.PackedPayload` — int8/int4 level buffers +
  per-unit scales + a per-unit bit-width vector (constant, or waterfilled
  from the round's Eq. 3 divergence stats when ``bits="auto"``) — and the
  whole dequant → EF-residual-update → masked weighted-accumulate chain
  runs in one pass per tile through the fused uplink kernel
  (``kernels/uplink``), never materialising per-client fp32
  reconstructions. Comm accounting prices the payload's actual wire bytes
  (``PackedPayload.unit_wire_bytes``) via ``unit_bytes_override``.
- **legacy** (``fused=False``): the pre-wire-format chain —
  ``transform_upload`` rebuilds fp32 ``Θ̂`` per client, ``update_residual``
  gates the EF rows, the inner strategy aggregates — kept as the unfused
  A/B reference (``benchmarks/kernel_bench.py``) and the equivalence
  target for the packed path's trajectory tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import aggregation as agg
from repro.core import wire as wire_mod
from repro.core.compress import compress_upload
from repro.core.units import tree_sub
from repro.core.wire import CompressionConfig
from repro.federated.strategies.base import FLStrategy
from repro.kernels import ops as kops
from repro.telemetry import profiling as prof_mod


class QuantizedUpload(FLStrategy):
    """Wrap ``inner`` with int-b delta quantization (+ error feedback)."""

    supports_scan = False       # quantized uploads need stacked clients
    supports_quantize = False   # no double-wrapping

    def __init__(self, inner: FLStrategy, cfg,
                 comp: CompressionConfig | None = None):
        super().__init__(cfg)
        if comp is None:
            comp = getattr(cfg, "compression", None)
        if comp is None:
            # duck-typed legacy cfg: only the flat knobs exist
            bits = int(getattr(cfg, "quantize_bits", 0))
            assert bits > 0
            comp = CompressionConfig(
                bits=bits,
                error_feedback=bool(getattr(cfg, "error_feedback", False)))
        assert type(inner).supports_quantize, inner.name
        self.comp = comp
        self.inner = inner
        self.name = f"{inner.name}+q{comp.bits}"
        # mirror the inner strategy's declared behaviour (instance attrs
        # shadow the class-level flags)
        self.needs_divergence = inner.needs_divergence or comp.is_auto
        self.supports_mesh = inner.supports_mesh
        self.eq5_weighted = inner.eq5_weighted
        self.tracks_residuals = comp.error_feedback
        self.packed_upload = comp.fused
        self.transforms_upload = not comp.fused

    # ---- cross-round state: inner state + the EF residual store ----
    def init_state(self, params, num_clients, mesh=None):
        # the error-feedback residual store is *declared* here as the
        # client state entry "residual" — the engines thread it like any
        # other strategy state (no special-cased plumbing in server.py)
        state = self.inner.init_state(params, num_clients, mesh)
        if self.tracks_residuals:
            from repro.launch.sharding import init_residual_store
            state = dict(state or {})
            client = dict(state.get("client") or {})
            client["residual"] = init_residual_store(params, num_clients,
                                                     mesh)
            state["client"] = client
        return state

    def select_with_state(self, state, divs, key, k, u, n):
        return self.inner.select_with_state(state, divs, key, k, u, n)

    def update_state(self, state, selection, divs, umap, key=None):
        # the engine already advanced the "residual" rows (via the packed
        # uplink or update_residual); the inner strategy's transition must
        # preserve entries it does not own (the default identity does)
        return self.inner.update_state(state, selection, divs, umap,
                                       key=key)

    # ---- delegated hooks ----
    def select(self, divs, key, k, u, n):
        return self.inner.select(divs, key, k, u, n)

    def telemetry_taps(self, state, selection, divs, umap):
        # a custom inner tap hook survives composition; the engines tap
        # the wrapper's EF residual norms via the client-state seam and
        # the packed wire bytes via the round's wire accounting.
        return self.inner.telemetry_taps(state, selection, divs, umap)

    def psum_parts(self, uploads, umap, sel_loc, data_sizes,
                   global_params=None):
        return self.inner.psum_parts(uploads, umap, sel_loc, data_sizes,
                                     global_params=global_params)

    def psum_finalize(self, parts, denom, umap, params_shard, fallback):
        return self.inner.psum_finalize(parts, denom, umap, params_shard,
                                        fallback)

    # ==================================================================
    # Packed wire-format path (CompressionConfig.fused)
    # ==================================================================
    def uplink_psum_parts(self, locals_, global_params, umap, sel_rows,
                          divs, data_sizes, res_rows):
        """Stacked locals → packed payload → fused kernel reduction.

        Returns ``(num_parts, denom, new_res_rows, wire)`` where
        ``num_parts`` is the param-structured additive Eq. 5 numerator
        ``Σ_k w[k,u]·Θ̂_k = denom_u·Ĝ + Σ_k w·scale·levels`` (the second
        term via the fused uplink kernel), ``denom`` the ``(U,)`` local
        weight sums, and ``wire`` the payload's byte accounting. Additive
        over clients, so the round reduces the parts exactly like the
        legacy ``psum_parts`` output, then calls :meth:`psum_finalize`.
        """
        comp = self.comp
        k = sel_rows.shape[0]
        bits = comp.bits_vector(umap, divs)                  # (U,) f32
        with prof_mod.phase("fl.eq5"):
            w, denom = agg.unit_weights(sel_rows, data_sizes)  # (K,U), (U,)
        ef = res_rows is not None

        def quantize_one(loc, res):
            delta = tree_sub(loc, global_params)
            if res is not None:
                # Δ+e in the leaf dtype first (bit-compat with the legacy
                # chain's bf16 rounding), then fp32 for the kernel
                v = jax.tree.map(
                    lambda d, e: (d + e.astype(d.dtype)).astype(jnp.float32),
                    delta, res)
            else:
                v = jax.tree.map(lambda d: d.astype(jnp.float32), delta)
            levels, scales = wire_mod.quantize_units(v, umap, bits)
            return jax.tree.map(lambda l: l.astype(jnp.int8), levels), \
                scales, v

        if ef:
            levels_k, scales_k, v_k = jax.vmap(quantize_one)(locals_,
                                                             res_rows)
        else:
            levels_k, scales_k, v_k = jax.vmap(
                lambda loc: quantize_one(loc, None))(locals_)

        # materialise the wire format (nibble-packs when every width ≤ 4);
        # nbytes/unit_wire_bytes below are computed from THIS payload
        payload = wire_mod.PackedPayload(
            wire_mod.pack_levels(levels_k, comp.storage_bits),
            scales_k, bits, storage_bits=comp.storage_bits)
        levels_k = wire_mod.unpack_levels(payload, v_k)

        # the quantize/pack half above runs in the engine's fl.uplink
        # phase; the fused dequant+EF+accumulate half below is Eq. 5
        with prof_mod.phase("fl.eq5"):
            num_parts = {}
            res_parts = {} if ef else None
            for key, (off, n) in umap.spans.items():
                w_seg = jax.lax.dynamic_slice(w, (0, off), (k, n))
                s_seg = jax.lax.dynamic_slice(scales_k, (0, off), (k, n))
                g_seg = jax.lax.dynamic_slice(sel_rows, (0, off), (k, n))
                d_seg = jax.lax.dynamic_slice(denom, (off,), (n,))

                def reduce_leaf(lv, vv, ee, g_leaf):
                    # lv/vv/ee: (K, n, ...) stacked or (K, ...); flatten the
                    # trailing dims so each unit is one kernel row
                    lv2 = lv.reshape(k, n, -1)
                    v2 = vv.reshape(k, n, -1)
                    g2 = g_leaf.astype(jnp.float32).reshape(n, -1)
                    if ee is not None:
                        e2 = ee.reshape(k, n, -1)
                        num2, res2 = kops.fused_uplink_ef(lv2, s_seg, w_seg,
                                                          g_seg, v2, e2)
                    else:
                        num2 = kops.fused_uplink(lv2, s_seg, w_seg)
                        res2 = None
                    # Σ_k w·Θ̂ = denom·Ĝ + Σ_k w·recon (the kernel term)
                    num2 = num2 + d_seg[:, None] * g2
                    num = num2.reshape(g_leaf.shape).astype(jnp.float32)
                    res = (None if res2 is None
                           else res2.reshape((k,) + g_leaf.shape))
                    return num, res

                glob = global_params[key]
                if ef:
                    out = jax.tree.map(reduce_leaf, levels_k[key], v_k[key],
                                       res_rows[key], glob)
                else:
                    out = jax.tree.map(
                        lambda lv, vv, g_leaf: reduce_leaf(lv, vv, None,
                                                           g_leaf),
                        levels_k[key], v_k[key], glob)
                num_parts[key] = jax.tree.map(lambda o: o[0], out,
                                              is_leaf=lambda o: isinstance(
                                                  o, tuple))
                if ef:
                    res_parts[key] = jax.tree.map(lambda o: o[1], out,
                                                  is_leaf=lambda o: isinstance(
                                                      o, tuple))

        wire = {"unit_bytes": payload.unit_wire_bytes(umap),
                "bits": bits, "nbytes": payload.nbytes}
        return num_parts, denom, res_parts, wire

    # ==================================================================
    # Legacy unfused chain (CompressionConfig.fused=False)
    # ==================================================================
    def transform_upload(self, local, global_params, umap, residual):
        # Θ̂ = Ĝ + dequant(Q_b(Δ + e)); divergence feedback (Eq. 3) was
        # already computed on the TRUE local model by the engine, so only
        # the uploaded payload is affected.
        return compress_upload(local, global_params, umap,
                               int(self.comp.bits), residual)

    def update_residual(self, cand_res, old_res, sel_row, umap,
                        global_params):
        # residuals advance only where a layer was actually uploaded
        # (s[k,u] = 1); elsewhere the old residual is carried forward.
        gate = umap.expand_to_leaves(cand_res, sel_row)
        old = old_res if old_res is not None else \
            agg.streaming_init(global_params)
        return jax.tree.map(lambda g_, n_, o_: g_ * n_ + (1 - g_) * o_,
                            gate, cand_res, old)

    # ==================================================================
    def comm_profile(self, selection, umap, param_bytes_override=None,
                     unit_bytes_override=None):
        if unit_bytes_override is None:
            if not self.comp.fused:
                # legacy pricing: uniform b/8 bytes per parameter
                return self.inner.comm_profile(
                    selection, umap,
                    param_bytes_override=int(self.comp.bits) / 8.0)
            # packed pricing at the configured widths; "auto" prices at
            # the avg_bits budget when no per-round vector is available
            # (the engines pass the round's actual allocation through
            # unit_bytes_override)
            b = (float(self.comp.avg_bits) if self.comp.is_auto
                 else float(int(self.comp.bits)))
            p = jnp.asarray(umap.unit_params, jnp.float32)
            unit_bytes_override = (jnp.ceil(p * b / 8.0)
                                   + wire_mod.UNIT_HEADER_BYTES)
        return self.inner.comm_profile(
            selection, umap, unit_bytes_override=unit_bytes_override)
