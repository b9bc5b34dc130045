"""DeepSeek-V2-Lite fine-tuned federatedly through LoRA adapters.

Program side: ``repro.models.transformer`` (multi-head latent attention
with YaRN rotary embeddings, the leading dense layer under ``dense``, the
dropless MoE layers on grouped matmuls under ``blocks``) with
``repro.models.lora`` adapters, the adapters the trainable partition,
through ``run_training_scan``. The dataset, the partition and the
program's loss are those of the Llama-architecture cell
(``bench/models/lora_transformer.py``).

Reference side, written out below from the configuration file and the
published model (arXiv:2405.04434; ``modeling_deepseek.py``): token
embedding; per layer an RMSNorm, then latent attention:

- ``q = h Wq``, per head ``[q_nope (128), q_pe (64)]``;
- ``[c, k_pe] = h Wkv_a``, ``c`` the 512-dim latent, RMS-normed with its
  own weight; ``[k_nope, v] = c Wkv_b``, per head 128 + 128;
- rotary embedding on ``q_pe`` and on ``k_pe`` (one head all heads share):
  YaRN inverse frequencies from the file's ``rope_scaling``, the 64 dims
  taken as pairs ``(2i, 2i+1)``, de-interleaved and rotated half against
  half;
- causal softmax attention over ``[q_nope, q_pe] . [k_nope, k_pe]`` at
  scale ``192^-0.5 mscale(40, 0.707)^2``, then ``o Wo``;

a residual add, an RMSNorm, and either the dense layer's SwiGLU (the
first ``first_k_dense_replace`` layers) or the MoE layer: router scores
``softmax(h Wg)`` in float32, the top ``num_experts_per_tok`` weights
(renormalised only if ``norm_topk_prob``) times ``routed_scaling_factor``
put into a dense (tokens, experts) weight matrix that is zero elsewhere,
every expert's SwiGLU computed on every token (in blocks of experts, so
that it fits) and summed with those weights, plus the shared experts'
SwiGLU; a residual add. Then a final RMSNorm, the untied head and the
mean next-token cross-entropy. Each adapted projection adds
``(x @ a) @ b``, an expert's with that expert's own factors. No sort, no
grouping and no capacity: a token routed to an expert is computed there.
The frozen base stays in its stored dtype and is widened to float32 a
layer, or a block of experts, at a time inside checkpointed steps.
"""
from __future__ import annotations

import math

import numpy as np

from bench.models.lora_transformer import Workload as LoraWorkload
from bench.models.lora_transformer import _mm, _rms
from bench.models.vgg9 import _Frozen, _keys

#: experts per step of the reference's scan over experts
EXPERT_BLOCK = 8
#: tokens per step of the reference's scan over the head and loss
LOSS_CHUNK = 1024


def dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {"d": cfg["hidden_size"], "H": h, "nope": nope, "rope": rope,
            "qk": nope + rope, "vd": cfg["v_head_dim"],
            "c": cfg["kv_lora_rank"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "f": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
            "fe": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "k": cfg["num_experts_per_tok"], "r": cfg["lora"]["rank"]}


def proj_shapes(cfg: dict, kind: str) -> dict:
    """{module path: {name: (d_in, d_out)}} of a layer's projections; a
    routed expert's are per expert."""
    m = dims(cfg)
    out = {"attn": {"wq": (m["d"], m["H"] * m["qk"]),
                    "wkv_a": (m["d"], m["c"] + m["rope"]),
                    "wkv_b": (m["c"], m["H"] * (m["nope"] + m["vd"])),
                    "wo": (m["H"] * m["vd"], m["d"])}}

    def swiglu(f):
        return {"w_gate": (m["d"], f), "w_up": (m["d"], f),
                "w_down": (f, m["d"])}

    if kind == "dense":
        out["mlp"] = swiglu(m["f"])
    else:
        out["moe"] = swiglu(m["fe"])
        out["moe/shared"] = swiglu(m["fs"])
    return out


def init_weights(jax, key, cfg: dict):
    """The whole tree in its stored dtype, as the program lays it out:
    embed/tok; dense/{ln1, attn/{wq, wkv_a, kv_norm, wkv_b, wo, lora}, ln2,
    mlp/{w_*, lora}} stacked over the leading dense layers;
    blocks/{ln1, attn/..., ln2, moe/{router, w_* (E,...), lora (E,...),
    shared/{w_*, lora}}} stacked over the MoE layers; final/{norm, head}.
    Projections and the router N(0, 1/d_in), embedding N(0, 0.02^2),
    norms one, LoRA ``a`` N(0, 1/d_in) and ``b`` zero."""
    jnp = jax.numpy
    dt = jnp.dtype(cfg["param_dtype"])
    m = dims(cfg)
    keys = iter(jax.random.split(key, 64))
    targets = cfg["lora"]["targets"]

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, dt) * std).astype(dt)

    def stack(kind, depth):
        t = {"ln1": jnp.ones((depth, m["d"]), dt),
             "ln2": jnp.ones((depth, m["d"]), dt)}
        for path, projs in proj_shapes(cfg, kind).items():
            lead = (depth, m["E"]) if path == "moe" else (depth,)
            sub, lora = {}, {}
            for name, (din, dout) in projs.items():
                sub[name] = normal(lead + (din, dout), 1.0 / np.sqrt(din))
                if name in targets.get(path, ()):
                    lora[name] = {
                        "a": normal(lead + (din, m["r"]), 1.0 / np.sqrt(din)),
                        "b": jnp.zeros(lead + (m["r"], dout), dt)}
            sub["lora"] = lora
            node = t
            *parents, mod = path.split("/")
            for p in parents:
                node = node[p]
            node[mod] = sub
        t["attn"]["kv_norm"] = jnp.ones((depth, m["c"]), dt)
        if kind == "moe":
            t["moe"]["router"] = normal((depth, m["d"], m["E"]),
                                        1.0 / np.sqrt(m["d"]))
        return t

    return {"embed": {"tok": normal((m["V"], m["d"]), 0.02)},
            "dense": stack("dense", m["dense"]),
            "blocks": stack("moe", m["L"] - m["dense"]),
            "final": {"norm": jnp.ones((m["d"],), dt),
                      "head": normal((m["d"], m["V"]), 1.0 / np.sqrt(m["d"]))}}


# ----------------------------------------------------------------------
# plain reference
# ----------------------------------------------------------------------
def yarn(cfg: dict):
    """(inverse frequencies (rope/2,) float64, cos/sin factor, softmax
    scale) from the file's ``rope_scaling`` (arXiv:2309.00071 as the
    published DeepSeek-V2 module applies it)."""
    m = dims(cfg)
    rs, dim, base = cfg["rope_scaling"], m["rope"], float(cfg["rope_theta"])
    factor = float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    def mscale(m_):
        return 0.1 * m_ * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    cos_factor = mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    scale = m["qk"] ** -0.5 * mscale(rs["mscale_all_dim"]) ** 2
    return inv, cos_factor, scale


def _rope_pairs(jax, x, inv, factor):
    """x: (B, T, heads, rope); DeepSeek's layout: pairs (2i, 2i+1)
    de-interleaved, then rotate-half."""
    jnp = jax.numpy
    t = x.shape[1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1)


def _proj(jax, h, base, lora, name, od):
    y = _mm(jax, h, base[name], od)
    ad = lora.get(name)
    if ad is not None:
        y = y + _mm(jax, _mm(jax, h, ad["a"], od), ad["b"], od)
    return y


def _swiglu(jax, h, base, lora, od):
    g = _proj(jax, h, base, lora, "w_gate", od)
    u = _proj(jax, h, base, lora, "w_up", od)
    return _proj(jax, jax.nn.silu(g) * u, base, lora, "w_down", od)


def _mla(jax, x, base, lora, cfg, od):
    jnp = jax.numpy
    m = dims(cfg)
    b, t, _ = x.shape
    inv, cos_factor, scale = yarn(cfg)
    q = _proj(jax, x, base, lora, "wq", od).reshape(b, t, m["H"], m["qk"])
    kv_a = _proj(jax, x, base, lora, "wkv_a", od)
    c = _rms(jax, kv_a[..., :m["c"]], base["kv_norm"], cfg["rms_norm_eps"])
    kv = _proj(jax, c, base, lora, "wkv_b", od).reshape(
        b, t, m["H"], m["nope"] + m["vd"])
    k_pe = _rope_pairs(jax, kv_a[..., None, m["c"]:], inv, cos_factor)
    q = jnp.concatenate([q[..., :m["nope"]],
                         _rope_pairs(jax, q[..., m["nope"]:], inv,
                                     cos_factor)], axis=-1)
    k = jnp.concatenate([kv[..., :m["nope"]],
                         jnp.repeat(k_pe, m["H"], axis=2)], axis=-1)
    v = kv[..., m["nope"]:]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(_, qkv):                   # one head at a time, so it fits
        qh, kh, vh = qkv
        s = jnp.einsum("bqd,bkd->bqk", qh, kh,
                       precision=jax.lax.Precision.HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return None, jnp.einsum("bqk,bkd->bqd", p, vh,
                                precision=jax.lax.Precision.HIGHEST)

    heads = lambda a: jnp.moveaxis(a, 2, 0)
    _, o = jax.lax.scan(head, None, (heads(q), heads(k), heads(v)))
    o = jnp.moveaxis(o, 0, 2)                          # (B, T, H, vd)
    return _proj(jax, o.reshape(b, t, m["H"] * m["vd"]), base, lora, "wo",
                 od)


def route_weights(jax, h, router, cfg):
    """(tokens, E) float32: the top-k routing weights of each token in
    its chosen experts' columns, zero elsewhere."""
    jnp = jax.numpy
    m = dims(cfg)
    logits = jnp.einsum("td,de->te", h, router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m["k"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, m["E"], dtype=jnp.float32)
                   * w[..., None], axis=1)


def _moe(jax, x, base, lora, cfg, od):
    """Every expert on every token, weighted by the dense routing
    weights, in a checkpointed scan over blocks of experts."""
    jnp = jax.numpy
    m = dims(cfg)
    b, t, d = x.shape
    h = x.reshape(b * t, d)
    weights = route_weights(jax, h, base["router"], cfg)       # (T, E)
    nb = m["E"] // EXPERT_BLOCK

    def blocks(a):   # (E, ...) -> (nb, EXPERT_BLOCK, ...)
        return a.reshape((nb, EXPERT_BLOCK) + a.shape[1:])

    experts = {n: blocks(base[n]) for n in ("w_gate", "w_up", "w_down")}
    adapters = jax.tree.map(blocks, {n: a for n, a in lora.items()
                                     if n != "shared"})
    cols = weights.T.reshape(nb, EXPERT_BLOCK, b * t)

    @jax.checkpoint
    def step(acc, xs):
        ws, ad, col = xs
        one = jax.vmap(lambda w_e, a_e: _swiglu(jax, h, w_e, a_e, od))
        y = one(ws, ad)                                # (EXPERT_BLOCK, T, d)
        return acc + jnp.einsum("etd,et->td", y, col,
                                precision=jax.lax.Precision.HIGHEST), None

    routed, _ = jax.lax.scan(step, jnp.zeros((b * t, d), jnp.float32),
                             (experts, adapters, cols))
    shared = _swiglu(jax, h, base["shared"], lora.get("shared", {}), od)
    return (routed + shared).reshape(b, t, d)


def reference_layer(jax, x, base, lora, cfg, control: bool, kind: str):
    """One decoder layer in float32; ``base``/``lora`` hold this layer's
    slices."""
    jnp = jax.numpy
    eps = cfg["rms_norm_eps"]
    od = jnp.dtype(cfg["control"]["operand_dtype"]) if control else None
    h = _rms(jax, x, base["ln1"], eps)
    x = x + _mla(jax, h, base["attn"], lora["attn"], cfg, od)
    h = _rms(jax, x, base["ln2"], eps)
    if kind == "dense":
        return x + _swiglu(jax, h, base["mlp"], lora["mlp"], od)
    return x + _moe(jax, h, base["moe"], lora["moe"], cfg, od)


def _adapters(tree):
    """Every module's ``lora`` subtree, keyed as the layer reads it."""
    out = {}
    for mod, sub in tree.items():
        if not isinstance(sub, dict):
            continue
        out[mod] = dict(sub.get("lora", {}))
        if "shared" in sub:
            out[mod]["shared"] = sub["shared"].get("lora", {})
    return out


def layer_keys(depth: int) -> list:
    """The trainable tree's top-level key of each MoE layer (see
    :meth:`Workload.split`), in the program's unit order."""
    width = len(str(depth - 1))
    return [f"blocks_{i:0{width}d}" for i in range(depth)]


def reference_loss(jax, trainable, frozen, tokens, labels, cfg, control):
    """``trainable`` as :meth:`Workload.split` lays it out."""
    jnp = jax.numpy
    m = dims(cfg)
    x = frozen["embed"]["tok"][tokens].astype(jnp.float32)
    layers = (("dense", "dense", trainable["dense"]),
              ("blocks", "moe", jax.tree.map(
                  lambda *a: jnp.stack(a),
                  *[trainable[k] for k in layer_keys(m["L"] - m["dense"])])))
    for stack, kind, lora in layers:
        # a scan over the stacked layers slices one layer at a time
        @jax.checkpoint
        def layer(x, xs, kind=kind):
            base, ad = xs
            return reference_layer(jax, x, base, _adapters(ad), cfg,
                                   control, kind), None

        x, _ = jax.lax.scan(layer, x, (frozen[stack], lora))
    x = _rms(jax, x, frozen["final"]["norm"], cfg["rms_norm_eps"])
    od = jnp.dtype(cfg["control"]["operand_dtype"]) if control else None
    b, t, d = x.shape
    size = math.gcd(t, LOSS_CHUNK)

    @jax.checkpoint
    def chunk(total, xs):               # the head and loss by token chunks
        xc, yc = xs
        logp = jax.nn.log_softmax(_mm(jax, xc, frozen["final"]["head"], od),
                                  axis=-1)
        nll = -jnp.take_along_axis(logp, yc[..., None], axis=-1)
        return total + jnp.sum(nll), None

    def chunks(a):
        return jnp.moveaxis(a.reshape((b, t // size, size) + a.shape[2:]),
                            1, 0)

    total, _ = jax.lax.scan(chunk, jnp.float32(0.0),
                            (chunks(x), chunks(labels)))
    return total / (b * t)


class Workload(LoraWorkload):
    alter_key = "blocks/moe/lora"

    def make_weights(self):
        import jax
        return jax.jit(init_weights, static_argnums=(0, 2))(
            jax, _keys(jax, self.seed, 3), _Frozen(self.config))

    def model_config(self):
        from repro.models.config import ModelConfig
        c = self.config
        rs = c["rope_scaling"]
        return ModelConfig(
            name=c["name"], family="moe",
            num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_attention_heads"],
            d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"],
            rope_factor=float(rs["factor"]),
            rope_original_max_positions=rs[
                "original_max_position_embeddings"],
            yarn_beta_fast=float(rs["beta_fast"]),
            yarn_beta_slow=float(rs["beta_slow"]),
            yarn_mscale=float(rs["mscale"]),
            yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
            num_experts=c["n_routed_experts"],
            num_shared_experts=c["n_shared_experts"],
            moe_top_k=c["num_experts_per_tok"],
            moe_d_ff=c["moe_intermediate_size"],
            norm_topk_prob=bool(c["norm_topk_prob"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            first_dense_layers=c["first_k_dense_replace"],
            dense_d_ff=c["intermediate_size"], aux_loss_coef=0.0,
            param_dtype=c["param_dtype"], compute_dtype=c["param_dtype"],
            remat_blocks=bool(c["remat_blocks"]))

    def split(self, params):
        """(trainable, frozen), with each MoE layer's adapters under a
        top-level key of its own (:func:`layer_keys`) in place of the
        stacked ``blocks``: one layer unit per key. The harness's
        reference aggregation (``bench/fedref.py``) reads a stacked key's
        unit count from the leading axis of client-stacked trees, where
        that axis holds the clients; per-layer keys keep its units right
        at any depth. The units are the program's (``blocks/i``, one per
        MoE layer, and ``dense``)."""
        import jax
        train, frozen = super().split(params)
        blocks = train.pop("blocks")
        depth = jax.tree.leaves(blocks)[0].shape[0]
        for i, key in enumerate(layer_keys(depth)):
            train[key] = jax.tree.map(lambda a, i=i: a[i], blocks)
        return train, frozen

    def reference_locals(self, trainable, frozen, idx, control: bool):
        import jax
        fn = _reference_client_fn(jax, _Frozen(self.config),
                                  _Frozen(self.traffic), control)
        outs = [fn(trainable, frozen, self.xs[i], self.ys[i]) for i in idx]
        locals_ = jax.tree.map(lambda *xs: jax.numpy.stack(xs),
                               *[o[0] for o in outs])
        return locals_, sum(float(o[1]) for o in outs) / len(outs)

    def costs(self) -> dict:
        from bench.costs import deepseek_v2_lite
        return {"flops_per_round": deepseek_v2_lite.useful_flops_per_round(
            self.config, self.traffic)}


_REF_FNS: dict = {}


def _reference_client_fn(jax, cfg, traffic, control: bool):
    """Jitted (trainable, frozen, tokens (B,T), labels) -> (local, loss):
    ``local_steps`` SGD steps on the adapters, each step's result stored
    in the adapters' own dtype as the configuration states."""
    key = (hash(cfg), hash(traffic), control)
    if key in _REF_FNS:
        return _REF_FNS[key]
    jnp = jax.numpy
    lr, steps = traffic["lr"], traffic["local_steps"]

    @jax.jit
    def fn(trainable, frozen, tokens, labels):
        losses = []
        p = trainable
        for _ in range(steps):
            loss, g = jax.value_and_grad(
                lambda t: reference_loss(jax, t, frozen, tokens, labels,
                                         cfg, control))(p)
            losses.append(loss)
            p = jax.tree.map(lambda a, b: (a.astype(jnp.float32) - lr * b)
                             .astype(a.dtype), p, g)
        return p, jnp.mean(jnp.stack(losses))

    _REF_FNS[key] = fn
    return fn

