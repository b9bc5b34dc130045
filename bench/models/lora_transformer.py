"""A Llama-architecture decoder fine-tuned federatedly through LoRA adapters.

Program side: ``repro.models.transformer`` with ``repro.models.lora``
adapters, the adapters the trainable partition, through
``run_training_scan``.

Reference side, written out below from the configuration file: token
embedding; per layer an RMSNorm (eps from the file), grouped-query causal
attention with rotary embeddings (theta from the file, rotate-half
layout, query head ``h`` reading key/value head ``h // (heads / kv
heads)``), a residual add, an RMSNorm, a SwiGLU MLP, a residual add; a
final RMSNorm and an untied head; mean next-token cross-entropy. Each
adapted projection adds ``(x @ a) @ b``. The frozen base stays in its
stored dtype and is widened to float32 one layer at a time inside a
checkpointed layer, so the float32 reference fits beside it.

The dataset is the benchmark's own, made on the device from the seed:
each sequence belongs to one of ``num_domains`` domains; its tokens are
drawn from a Zipf law over the ranks of a domain's own permutation of
the whole vocabulary. Sequences are sorted by domain and split into
equal client shards, so clients are non-IID by domain.
"""
from __future__ import annotations

import json

import numpy as np

from bench.fedcell import FedCell
from bench.models.vgg9 import _Frozen, _keys

def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    return {"d": d, "f": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "H": cfg["num_attention_heads"],
            "KV": cfg["num_key_value_heads"], "hd": hd,
            "q": cfg["num_attention_heads"] * hd,
            "kv": cfg["num_key_value_heads"] * hd,
            "r": cfg["lora"]["rank"]}


def proj_shapes(cfg: dict) -> dict:
    """{module: {name: (d_in, d_out)}} of the adapted projections."""
    m = dims(cfg)
    return {"attn": {"wq": (m["d"], m["q"]), "wk": (m["d"], m["kv"]),
                     "wv": (m["d"], m["kv"]), "wo": (m["q"], m["d"])},
            "mlp": {"w_gate": (m["d"], m["f"]), "w_up": (m["d"], m["f"]),
                    "w_down": (m["f"], m["d"])}}


def init_weights(jax, key, cfg: dict):
    """The whole tree in its stored dtype, as the program lays it out:
    embed/tok, blocks/{ln1, attn/{w*, lora/*}, ln2, mlp/{w*, lora/*}},
    final/{norm, head}. Dense N(0, 1/d_in), embedding N(0, 0.02^2), norms
    one, LoRA ``a`` N(0, 1/d_in) and ``b`` zero."""
    jnp = jax.numpy
    dt = jnp.dtype(cfg["param_dtype"])
    m = dims(cfg)
    L, r = m["L"], m["r"]
    keys = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, dt) * std).astype(dt)

    blocks = {"ln1": jnp.ones((L, m["d"]), dt),
              "ln2": jnp.ones((L, m["d"]), dt)}
    for mod, projs in proj_shapes(cfg).items():
        sub = {}
        lora = {}
        for name, (din, dout) in projs.items():
            sub[name] = normal((L, din, dout), 1.0 / np.sqrt(din))
            if name in cfg["lora"]["targets"][mod]:
                lora[name] = {"a": normal((L, din, r), 1.0 / np.sqrt(din)),
                              "b": jnp.zeros((L, r, dout), dt)}
        sub["lora"] = lora
        blocks[mod] = sub
    return {"embed": {"tok": normal((m["V"], m["d"]), 0.02)},
            "blocks": blocks,
            "final": {"norm": jnp.ones((m["d"],), dt),
                      "head": normal((m["d"], m["V"]), 1.0 / np.sqrt(m["d"]))}}


def make_tokens(jax, key, n_seq: int, seq_len: int, vocab: int,
                n_domains: int, zipf: float):
    """(n_seq, seq_len) int32 tokens and (n_seq,) int32 domains."""
    jnp = jax.numpy
    kd, kp, kt = jax.random.split(key, 3)
    domains = jax.random.randint(kd, (n_seq,), 0, n_domains)
    perms = jax.vmap(lambda k: jax.random.permutation(k, vocab))(
        jax.random.split(kp, n_domains))
    w = (jnp.arange(vocab, dtype=jnp.float32) + 1.0) ** (-zipf)
    cdf = jnp.cumsum(w) / jnp.sum(w)
    u = jax.random.uniform(kt, (n_seq, seq_len))
    ranks = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
    return (perms[domains[:, None], ranks].astype(jnp.int32),
            domains.astype(jnp.int32))


# ----------------------------------------------------------------------
# plain reference
# ----------------------------------------------------------------------
def _round_fwd(jax, x, dtype):
    """``x`` rounded to ``dtype`` in the forward pass; the cotangent
    passes through unrounded, so gradients stay float32 (an unscaled
    float8 cotangent would flush most of them to zero)."""
    x = x.astype(jax.numpy.float32)
    return x + jax.lax.stop_gradient(
        x.astype(dtype).astype(jax.numpy.float32) - x)


def _mm(jax, x, w, operand_dtype):
    """x @ w in float32 at "highest"; the control first rounds both
    operands to ``operand_dtype`` (the configuration's ``control``: one
    step below the bfloat16 it states)."""
    jnp = jax.numpy
    if operand_dtype is not None:
        x = _round_fwd(jax, x, operand_dtype)
        w = _round_fwd(jax, w, operand_dtype)
    return jnp.einsum("...d,df->...f", x, w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(jax, x, scale, eps):
    jnp = jax.numpy
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(jax, x, theta):
    """x: (B, T, heads, hd); rotate-half layout."""
    jnp = jax.numpy
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def reference_layer(jax, x, base, lora, cfg, control: bool):
    """One decoder layer in float32; ``base``/``lora`` hold this layer's
    slices."""
    jnp = jax.numpy
    m = dims(cfg)
    eps = cfg["rms_norm_eps"]
    od = jnp.dtype(cfg["control"]["operand_dtype"]) if control else None

    def proj(h, mod, name):
        y = _mm(jax, h, base[mod][name], od)
        ad = lora[mod].get(name)
        if ad is not None:
            y = y + _mm(jax, _mm(jax, h, ad["a"], od), ad["b"], od)
        return y

    b, t, _ = x.shape
    h = _rms(jax, x, base["ln1"], eps)
    q = proj(h, "attn", "wq").reshape(b, t, m["H"], m["hd"])
    k = proj(h, "attn", "wk").reshape(b, t, m["KV"], m["hd"])
    v = proj(h, "attn", "wv").reshape(b, t, m["KV"], m["hd"])
    q = _rope(jax, q, cfg["rope_theta"])
    k = _rope(jax, k, cfg["rope_theta"])
    group = m["H"] // m["KV"]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(m["hd"])
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(b, t, m["q"])
    x = x + proj(o, "attn", "wo")
    h = _rms(jax, x, base["ln2"], eps)
    g = proj(h, "mlp", "w_gate")
    u = proj(h, "mlp", "w_up")
    return x + proj(jax.nn.silu(g) * u, "mlp", "w_down")


def reference_loss(jax, trainable, frozen, tokens, labels, cfg, control):
    jnp = jax.numpy
    m = dims(cfg)
    x = frozen["embed"]["tok"][tokens].astype(jnp.float32)
    layer = jax.checkpoint(
        lambda x, base, lora: reference_layer(jax, x, base, lora, cfg,
                                              control))
    for i in range(m["L"]):
        base = jax.tree.map(lambda a: a[i], frozen["blocks"])
        lora = {mod: jax.tree.map(lambda a: a[i],
                                  trainable["blocks"][mod]["lora"])
                for mod in ("attn", "mlp")}
        x = layer(x, base, lora)
    x = _rms(jax, x, frozen["final"]["norm"], cfg["rms_norm_eps"])
    od = jnp.dtype(cfg["control"]["operand_dtype"]) if control else None
    logits = _mm(jax, x, frozen["final"]["head"], od)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return jnp.mean(nll)


class Workload(FedCell):
    data_keys = ("tokens", "labels")
    alter_key = "blocks/mlp/lora"

    def make_data(self) -> None:
        import jax
        jnp = jax.numpy
        cfg, tr = self.config, self.traffic
        ds = tr["dataset"]
        n_seq = tr["num_clients"] * ds["sequences_per_client"]
        make = jax.jit(make_tokens, static_argnums=(0, 2, 3, 4, 5, 6))
        tokens, domains = make(jax, _keys(jax, self.seed, 1), n_seq,
                               ds["seq_len"], cfg["vocab_size"],
                               ds["num_domains"], ds["zipf"])
        self.xs, self.ys = tokens[:, :-1], tokens[:, 1:]
        order = np.argsort(np.asarray(domains), kind="stable")
        self.parts = [np.sort(p) for p in
                      np.array_split(order, tr["num_clients"])]
        width = max(len(p) for p in self.parts)
        self.part_idx = jnp.asarray(np.stack(
            [p[np.arange(width) % len(p)] for p in self.parts]), np.int32)
        self.part_sizes = jnp.asarray([len(p) for p in self.parts],
                                      np.int32)

    def make_weights(self):
        import jax
        return jax.jit(init_weights, static_argnums=(0, 2))(
            jax, _keys(jax, self.seed, 3), _Frozen(self.config))

    def model_config(self):
        from repro.models.config import ModelConfig
        c = self.config
        return ModelConfig(
            name=c["name"], family="dense",
            num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], d_ff=c["intermediate_size"],
            vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
            param_dtype=c["param_dtype"], compute_dtype=c["param_dtype"],
            remat_blocks=bool(c["remat_blocks"]))

    def program_loss(self):
        # the other faults are planted outside the loss, so they share the
        # sound run's compiled block (a second one would not fit beside it)
        key = (json.dumps(self.config, sort_keys=True),
               self.fault == "half_batch")
        if key not in _LOSSES:
            from repro.models import transformer
            base = transformer.make_lm_loss(self.model_config())
            if self.fault == "half_batch":
                def loss(p, b):
                    return base(p, {k: v[:, :v.shape[1] // 2]
                                    for k, v in b.items()})
            else:
                loss = base
            _LOSSES[key] = loss
        return _LOSSES[key]

    def partition(self, params):
        from repro.models.lora import lora_partition
        return lora_partition(params)

    def split(self, params):
        from bench.fedref import leaf_items
        train, frozen = {}, {}
        for path, leaf in leaf_items(params):
            keys = path.split("/")
            node = train if "lora" in keys else frozen
            for k in keys[:-1]:
                node = node.setdefault(k, {})
            node[keys[-1]] = leaf
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
        from bench.costs import lora_transformer
        return {"flops_per_round": lora_transformer.useful_flops_per_round(
            self.config, self.traffic)}


_LOSSES: dict = {}
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
