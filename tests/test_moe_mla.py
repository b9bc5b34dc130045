"""DeepSeek-V2-Lite's mechanisms against plain float32 references, on the
CPU at small sizes: YaRN's constants, multi-head latent attention, the
dropless MoE layer (also where every token picks one expert, which a
fixed capacity would overflow), per-expert LoRA, the grouped matmul's two
kernels and their batching rule, the leading dense layer before the MoE
stack, and the tokens-per-expert telemetry tap."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.units import UnitMap
from repro.federated import FLConfig, build_round_fn
from repro.models import attention as attn
from repro.models import moe
from repro.models import transformer as tf
from repro.models.lora import inject_lora
from repro.telemetry import TelemetryConfig

HI = jax.lax.Precision.HIGHEST


def small(**kw):
    """The zoo's reduced DeepSeek-V2-Lite in float32 (MLA, YaRN, one
    dense layer, then MoE)."""
    cfg = get_config("deepseek-v2-lite").reduced()
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32", **kw)


def one_layer(tree, i=0):
    return jax.tree.map(lambda a: a[i], tree)


# ----------------------------------------------------------------------
# plain references
# ----------------------------------------------------------------------
def plain_rope(x, cfg):
    """Rotate each interleaved pair (2i, 2i+1) as one complex number, at
    the YaRN frequencies written out from the paper's formulas."""
    d = x.shape[-1]
    theta, factor = cfg.rope_theta, cfg.rope_factor
    orig = cfg.rope_original_max_positions

    def corr(rot):
        return d * np.log(orig / (rot * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(np.ceil(corr(cfg.yarn_beta_slow)), d - 1)
    freq = theta ** (-np.arange(0, d, 2) / d)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    freq = freq / factor * ramp + freq * (1 - ramp)
    pos = np.arange(x.shape[1])[:, None] * freq[None, :]
    rot = np.exp(1j * pos)[None, :, None, :]
    z = (np.asarray(x[..., 0::2], np.float64)
         + 1j * np.asarray(x[..., 1::2], np.float64)) * rot
    return z                                          # (B, T, H, d/2)


def plain_mla(p, cfg, x):
    """Causal latent attention with the latent expanded, head by head."""
    x = np.asarray(x, np.float64)
    b, t, _ = x.shape
    h, nope, vd, r = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                      cfg.kv_lora_rank)
    q = (x @ np.asarray(p["wq"])).reshape(b, t, h, -1)
    kv_a = x @ np.asarray(p["wkv_a"])
    c = kv_a[..., :r]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-6) \
        * np.asarray(p["kv_norm"])
    kv = (c @ np.asarray(p["wkv_b"])).reshape(b, t, h, nope + vd)
    q_pe = plain_rope(q[..., nope:], cfg)
    k_pe = plain_rope(kv_a[..., None, r:], cfg)
    m = attn.yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim)
    scale = (nope + cfg.qk_rope_head_dim) ** -0.5 * m * m
    out = np.zeros((b, t, h, vd))
    for i in range(h):
        s = (np.einsum("bqd,bkd->bqk", q[:, :, i, :nope], kv[:, :, i, :nope])
             + np.real(np.einsum("bqd,bkd->bqk", q_pe[:, :, i],
                                 np.conj(k_pe[:, :, 0]))))
        s = np.where(np.tril(np.ones((t, t), bool)), s * scale, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, :, i] = (w / w.sum(-1, keepdims=True)) @ kv[:, :, i, nope:]
    return out.reshape(b, t, h * vd) @ np.asarray(p["wo"])


def plain_swiglu(x, wg, wu, wd, lora=None):
    def proj(v, w, name):
        y = v @ w
        if lora and name in lora:
            y = y + (v @ np.asarray(lora[name]["a"])) @ np.asarray(
                lora[name]["b"])
        return y
    g = proj(x, np.asarray(wg), "w_gate")
    return proj(g / (1 + np.exp(-g)) * proj(x, np.asarray(wu), "w_up"),
                np.asarray(wd), "w_down")


def plain_moe(p, cfg, x):
    """Every expert on every token, weighted by a dense (T, E) matrix that
    is zero off each token's top-k: no sort, no grouping, no capacity."""
    b, s, d = x.shape
    xt = np.asarray(x, np.float64).reshape(b * s, d)
    logits = xt @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1)[:, :cfg.moe_top_k]
    w = np.take_along_axis(probs, top, -1)
    if cfg.norm_topk_prob:
        w /= w.sum(-1, keepdims=True)
    dense = np.zeros_like(probs)
    np.put_along_axis(dense, top, w * cfg.routed_scaling_factor, -1)
    lora = p.get("lora", {})
    out = np.zeros_like(xt)
    for e in range(cfg.num_experts):
        ad = {n: {"a": f["a"][e], "b": f["b"][e]} for n, f in lora.items()}
        out += dense[:, e:e + 1] * plain_swiglu(
            xt, p["w_gate"][e], p["w_up"][e], p["w_down"][e], ad)
    sh = p["shared"]
    out += plain_swiglu(xt, sh["w_gate"], sh["w_up"], sh["w_down"],
                        sh.get("lora"))
    return out.reshape(b, s, d), (dense > 0).sum(0)


# ----------------------------------------------------------------------
# YaRN
# ----------------------------------------------------------------------
def test_yarn_constants_at_published_settings():
    cfg = get_config("deepseek-v2-lite")
    assert attn.yarn_correction_range(64, 1e4, 4096, 32.0, 1.0) == (10, 23)
    assert tf.mla_softmax_scale(cfg) == pytest.approx(0.114721, abs=1e-6)
    inv = np.asarray(attn.rope_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0))
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)  # extrapolate
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < base[11:23]) & (inv[11:23] > base[11:23] / 40))
    # mscale / mscale_all_dim = 1: cos and sin are not rescaled
    assert attn.yarn_mscale(40.0, 0.707) / attn.yarn_mscale(40.0, 0.707) == 1


# ----------------------------------------------------------------------
# multi-head latent attention
# ----------------------------------------------------------------------
@pytest.mark.parametrize("t", [9, 40])
def test_mla_matches_plain_reference(t):
    cfg = small(rope_original_max_positions=16)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    p = one_layer(params["blocks"])["attn"]
    p = dict(p, kv_norm=1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), p["kv_norm"].shape))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(t)[None], (2, t))
    with jax.default_matmul_precision("highest"):
        got = tf._self_attn(p, cfg, x, pos)
    np.testing.assert_allclose(np.asarray(got), plain_mla(p, cfg, x),
                               rtol=2e-4, atol=2e-5)


def test_mla_long_sequence_takes_the_flash_path():
    """Past ``flash_threshold`` keys the chunked path must give the same
    output, with its own value width and scale."""
    cfg = small(attn_chunk=16)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    p = one_layer(params["blocks"])["attn"]
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 4, 24))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 4, 24))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 4, 16))
    pos = jnp.arange(40)
    short = attn.attend(q, k, v, q_pos=pos, kv_pos=pos, scale=0.3)
    flash = attn.attend(q, k, v, q_pos=pos, kv_pos=pos, scale=0.3, chunk=16,
                        flash_threshold=8)
    assert short.shape == (1, 40, 4, 16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(short),
                               rtol=1e-5, atol=1e-5)
    assert p["wkv_b"].shape[-1] == cfg.num_heads * (
        cfg.qk_nope_head_dim + cfg.v_head_dim)


# ----------------------------------------------------------------------
# dropless MoE
# ----------------------------------------------------------------------
@pytest.mark.parametrize("norm_topk", [False, True])
def test_dropless_moe_matches_dense_reference(norm_topk):
    cfg = small(norm_topk_prob=norm_topk, routed_scaling_factor=1.5)
    p = one_layer(tf.init_params(jax.random.PRNGKey(0), cfg)["blocks"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, _, tokens = moe.moe_fwd(p, x, cfg)
    want, counts = plain_moe(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tokens), counts)


def test_no_token_dropped_when_every_token_picks_one_expert():
    """All 2 x 16 tokens route to expert 2 first: 32 rows for one expert,
    far past the 1.25 x T k / E = 20 rows a capacity-based dispatch kept."""
    cfg = small()
    p = one_layer(tf.init_params(jax.random.PRNGKey(0), cfg)["blocks"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    x = x.at[..., 0].set(8.0)
    p = dict(p, router=p["router"].at[0, 2].set(40.0))
    with jax.default_matmul_precision("highest"):
        out, _, tokens = moe.moe_fwd(p, x, cfg)
    want, counts = plain_moe(p, cfg, x)
    assert counts[2] == 32 and float(tokens[2]) == 32.0
    assert float(tokens.sum()) == 32 * cfg.moe_top_k
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


def test_expert_lora_at_zero_b_is_the_base_bit_for_bit():
    cfg = get_config("deepseek-v2-lite").reduced()          # bfloat16
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    adapted = inject_lora(jax.random.PRNGKey(1), params, rank=2,
                          targets={"moe": ("w_gate", "w_up", "w_down"),
                                   "moe/shared": ("w_gate", "w_up",
                                                  "w_down")})
    lora = adapted["blocks"]["moe"]["lora"]
    assert lora["w_gate"]["a"].shape == (1, cfg.num_experts, cfg.d_model, 2)
    assert lora["w_down"]["b"].shape == (1, cfg.num_experts, 2, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (2, 9, cfg.d_model)).astype(jnp.bfloat16)
    fwd = jax.jit(lambda p: moe.moe_fwd(one_layer(p["blocks"])["moe"], x,
                                        cfg)[0])
    np.testing.assert_array_equal(np.asarray(fwd(params), np.float32),
                                  np.asarray(fwd(adapted), np.float32))


def test_expert_lora_matches_dense_reference():
    cfg = small()
    params = inject_lora(jax.random.PRNGKey(1),
                         tf.init_params(jax.random.PRNGKey(0), cfg), rank=3)
    p = one_layer(params["blocks"])["moe"]
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 16))
    p = jax.tree_util.tree_map_with_path(
        lambda path, a: (0.1 * jax.random.normal(next(keys), a.shape)
                         if "'b'" in jax.tree_util.keystr(path) else a), p)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 11, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, _, _ = moe.moe_fwd(p, x, cfg)
    want, _ = plain_moe(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------------
# the grouped matmul's kernels: megablox (run by the Pallas interpreter)
# and ragged_dot, against per-group products
# ----------------------------------------------------------------------
#: 723 rows (megablox pads them to 1,024 in the last group), an empty
#: first and fourth group and one group with 97 % of the rows
GROUPS = (0, 700, 3, 0, 20)


def lora_inputs(seed=0, din=128, dout=256, r=16, groups=GROUPS):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    e, m = len(groups), sum(groups)
    return (jax.random.normal(ks[0], (m, din)),
            jax.random.normal(ks[1], (e, din, dout)) / din ** 0.5,
            jax.random.normal(ks[2], (e, din, r)) / din ** 0.5,
            jax.random.normal(ks[3], (e, r, dout)),
            jax.random.normal(ks[4], (m, dout)),
            jnp.asarray(groups, jnp.int32))


def lora_product(kernel, x, w, a, b, gs):
    """An expert projection as ``moe._proj`` makes it: base plus the
    per-expert LoRA delta, each a grouped product."""
    gm = functools.partial(moe.grouped_matmul, group_sizes=gs, kernel=kernel)
    return gm(x, w) + gm(gm(x, a), b)


def plain_lora_product(x, w, a, b, gs, c):
    """(y, dL/dx, dL/da, dL/db) of L = Σ c·y, group by group in float64."""
    x, w, a, b, c = (np.asarray(v, np.float64) for v in (x, w, a, b, c))
    y, dx = np.zeros(c.shape), np.zeros(x.shape)
    da, db = np.zeros(a.shape), np.zeros(b.shape)
    lo = 0
    for e, n in enumerate(np.asarray(gs)):
        s = slice(lo, lo + n)
        y[s] = x[s] @ (w[e] + a[e] @ b[e])
        dx[s] = c[s] @ (w[e] + a[e] @ b[e]).T
        da[e] = x[s].T @ c[s] @ b[e].T
        db[e] = (x[s] @ a[e]).T @ c[s]
        lo += n
    return y, dx, da, db


@pytest.mark.parametrize("kernel", ["megablox_interpret", "ragged_dot"])
def test_grouped_matmul_and_its_vjp_match_per_group_products(kernel):
    """Rows not a multiple of megablox's 512-row tile, empty groups and a
    skewed group: the product and its gradients w.r.t. the rows and the
    per-expert LoRA factors (the frozen ``w`` takes none in training)."""
    x, w, a, b, c, gs = lora_inputs()
    loss = lambda x, a, b: jnp.sum(c * lora_product(kernel, x, w, a, b, gs))
    with jax.default_matmul_precision("highest"):
        y = lora_product(kernel, x, w, a, b, gs)
        grads = jax.grad(loss, argnums=(0, 1, 2))(x, a, b)
    want = plain_lora_product(x, w, a, b, gs, c)
    for got, ref in zip((y,) + grads, want):   # float32 sums of 700 rows
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4,
                                   atol=1e-5 * np.abs(ref).max())


def test_megablox_matches_ragged_dot_in_bfloat16():
    """The kernels the program picks on and off the chip, as the model
    calls them (bfloat16 rows and weights, bfloat16 results)."""
    bf = jnp.bfloat16
    x, w, a, b, c, gs = (v.astype(bf) if v.dtype == jnp.float32 else v
                         for v in lora_inputs(seed=1))
    outs = {}
    for kernel in ("megablox_interpret", "ragged_dot"):
        loss = lambda x, a, b: jnp.sum(
            c * lora_product(kernel, x, w, a, b, gs)).astype(jnp.float32)
        outs[kernel] = (lora_product(kernel, x, w, a, b, gs),) + jax.grad(
            loss, argnums=(0, 1, 2))(x, a, b)
    for got, ref in zip(outs["megablox_interpret"], outs["ragged_dot"]):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_allclose(got, ref, rtol=2e-2,
                                   atol=2e-2 * np.abs(ref).max())


@pytest.mark.parametrize("kernel", ["megablox_interpret", "ragged_dot"])
@pytest.mark.parametrize("shared", [True, False])
def test_vmapped_grouped_matmul_runs_each_client(kernel, shared):
    """The stacked clients of a vmap round: rows, groups and cotangents
    per client, the factors shared (first local step) or per client."""
    n = 3
    per = [lora_inputs(seed=i, din=128, dout=128, r=8,
                       groups=(5, 0, 40 + 7 * i, 2)) for i in range(n)]
    # equal row counts across clients: pad the last group
    m = max(p[0].shape[0] for p in per)
    stack = lambda i, pad=False: jnp.stack([
        jnp.pad(p[i], ((0, m - p[i].shape[0]), (0, 0))) if pad else p[i]
        for p in per])
    x, c = stack(0, True), stack(4, True)
    gs = jnp.stack([p[5].at[-1].add(m - p[0].shape[0]) for p in per])
    w = per[0][1]
    a, b = (per[0][2], per[0][3]) if shared else (stack(2), stack(3))

    def loss(x, a, b, gs, c):
        return jnp.sum(c * lora_product(kernel, x, w, a, b, gs))

    ab_axes = None if shared else 0
    with jax.default_matmul_precision("highest"):
        got = jax.vmap(jax.grad(loss, argnums=(0, 1, 2)),
                       in_axes=(0, ab_axes, ab_axes, 0, 0))(x, a, b, gs, c)
        for i in range(n):
            ai, bi = (a, b) if shared else (a[i], b[i])
            want = jax.grad(loss, argnums=(0, 1, 2))(x[i], ai, bi, gs[i],
                                                    c[i])
            for g, r in zip(got, want):
                np.testing.assert_allclose(np.asarray(g[i]), np.asarray(r),
                                           rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# the leading dense layer, then the MoE stack
# ----------------------------------------------------------------------
def test_dense_layer_then_moe_stack_matches_plain_reference():
    cfg = small(num_layers=3)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.leaves(params["dense"])[0].shape[0] == 1
    assert jax.tree.leaves(params["blocks"])[0].shape[0] == 2
    assert UnitMap.build(params).names == (
        "blocks/0", "blocks/1", "dense", "embed", "final")
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits, _ = tf.forward(params, cfg, toks)

    def rms(v, w):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + 1e-6) * w

    x = np.asarray(params["embed"]["tok"], np.float64)[np.asarray(toks)]
    layers = [("dense", 0)] + [("blocks", i) for i in range(2)]
    for stack, i in layers:
        blk = jax.tree.map(np.asarray, one_layer(params[stack], i))
        x = x + plain_mla(blk["attn"], cfg, rms(x, blk["ln1"]))
        h = rms(x, blk["ln2"])
        if stack == "dense":
            assert blk["mlp"]["w_gate"].shape[-1] == cfg.dense_d_ff
            x = x + plain_swiglu(h, blk["mlp"]["w_gate"], blk["mlp"]["w_up"],
                                 blk["mlp"]["w_down"])
        else:
            x = x + plain_moe(blk["moe"], cfg, h)[0]
    want = rms(x, np.asarray(params["final"]["norm"])) @ np.asarray(
        params["final"]["head"])
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-4,
                               atol=2e-4)


# ----------------------------------------------------------------------
# a whole FedLDF round: stacked clients (vmap) against sequential (scan)
# ----------------------------------------------------------------------
def test_moe_round_vmap_matches_scan():
    """The vmap round trains the stacked clients through the grouped
    matmul's batching rule; the scan round trains them one at a time."""
    cfg = small()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    k = 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (k, 2, 8), 0,
                              cfg.vocab_size)
    loss = tf.make_lm_loss(cfg)
    out = {}
    for mode in ("vmap", "scan"):
        fl = FLConfig(algo="fedldf", num_clients=4, clients_per_round=k,
                      top_n=2, lr=0.05, mode=mode)
        round_fn = jax.jit(build_round_fn(loss, UnitMap.build(params), fl))
        with jax.default_matmul_precision("highest"):
            out[mode] = round_fn(params, {"tokens": toks, "labels": toks},
                                 jnp.ones((k,)), jax.random.PRNGKey(2))[0]
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         out["scan"], params)
    assert max(jax.tree.leaves(moved)) > 1e-3
    for a, b in zip(jax.tree.leaves(out["vmap"]), jax.tree.leaves(out["scan"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


# ----------------------------------------------------------------------
# the tokens-per-expert tap
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["scan", "vmap"])
def test_expert_tokens_tap(mode):
    cfg = small()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    k, b, s = 3, 2, 8
    fl = FLConfig(algo="fedldf", num_clients=4, clients_per_round=k,
                  top_n=2, lr=0.01, mode=mode,
                  telemetry=TelemetryConfig(taps=True))
    loss = tf.make_lm_loss(cfg)
    round_fn = jax.jit(build_round_fn(loss, UnitMap.build(params), fl))
    toks = jax.random.randint(jax.random.PRNGKey(1), (k, b, s), 0,
                              cfg.vocab_size)
    _, metrics = round_fn(params, {"tokens": toks, "labels": toks},
                          jnp.ones((k,)), jax.random.PRNGKey(2))
    taps = metrics["taps"]
    tokens = np.asarray(taps["expert_tokens"])
    assert tokens.shape == (cfg.moe_layers, cfg.num_experts)
    np.testing.assert_array_equal(tokens.sum(-1), k * b * s * cfg.moe_top_k)
    want = tokens.max(-1) / tokens.mean(-1)
    np.testing.assert_allclose(np.asarray(taps["expert_load_ratio"]), want)
    one = np.asarray(tf.expert_load(params, cfg, {"tokens": toks[0]})
                     ["expert_tokens"])
    assert one.sum() == b * s * cfg.moe_top_k
