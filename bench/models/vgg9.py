"""VGG-9 on CIFAR-10-shaped images: the paper's model (§III-A).

Program side: ``repro.models.cnn`` through ``run_training_scan``.
Reference side: the forward pass written out below in plain ``jax.numpy``
from the configuration file (8 3x3 convolutions with SAME padding, each
followed by a bias, a batch-statistics normalisation with learned scale
and bias, and a ReLU; 2x2 max pooling after the convolutions the file
lists; one dense classifier; mean softmax cross-entropy).

The dataset is the benchmark's own, made in bulk from the seed:
per class a smooth random prototype (four sinusoids per channel, unit
variance), each image a prototype cyclically shifted by up to
``max_shift`` pixels plus Gaussian noise of ``noise``. Labels are uniform
over the classes; clients hold an IID split of equal shards.
"""
from __future__ import annotations

import numpy as np

from bench.fedcell import FedCell


def _keys(jax, seed: int, tag: int):
    """A key for one purpose, from all bits of the seed."""
    lo, hi = int(seed) % (2 ** 32), int(seed) // (2 ** 32)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi % (2 ** 31))
    return jax.random.fold_in(key, tag)


def make_images(seed: int, n: int, classes: int, size: int, channels: int,
                noise: float, max_shift: int):
    """(n, size, size, channels) f32 images and (n,) int32 labels, made in
    bulk on the host (an image array whose last axis is 3 wide is best
    laid out by the device's own transfer, not by a jitted gather)."""
    rng = np.random.default_rng([seed, 1])
    freqs = rng.standard_normal((classes, 4, 2))
    phases = rng.uniform(0.0, 2 * np.pi, (classes, 4, channels))
    amps = rng.standard_normal((classes, 4, channels))
    grid = np.linspace(0.0, 2 * np.pi, size)
    arg = (freqs[:, :, 0, None, None] * grid[None, None, :, None]
           + freqs[:, :, 1, None, None] * grid[None, None, None, :])
    waves = np.sin(arg[..., None] + phases[:, :, None, None, :])
    protos = np.sum(amps[:, :, None, None, :] * waves, axis=1)
    protos /= protos.std(axis=(1, 2, 3), keepdims=True) + 1e-8
    protos = protos.astype(np.float32)
    ys = rng.integers(0, classes, n)
    shift = rng.integers(-max_shift, max_shift + 1, (n, 2))
    pos = np.arange(size)
    rows = (pos[None, :] - shift[:, :1]) % size
    cols = (pos[None, :] - shift[:, 1:]) % size
    xs = protos[ys[:, None, None], rows[:, :, None], cols[:, None, :]]
    xs += np.float32(noise) * rng.standard_normal(xs.shape, np.float32)
    return xs, ys.astype(np.int32)


def init_weights(jax, key, cfg: dict):
    jnp = jax.numpy
    chans = cfg["channels"]
    ks = cfg["kernel_size"]
    keys = jax.random.split(key, len(chans) + 1)
    params, cin = {}, cfg["in_channels"]
    for i, cout in enumerate(chans):
        fan_in = ks * ks * cin
        params[f"conv{i}"] = {
            "w": jax.random.normal(keys[i], (ks, ks, cin, cout))
            * np.sqrt(2.0 / fan_in),
            "b": jnp.zeros((cout,)), "scale": jnp.ones((cout,)),
            "bias": jnp.zeros((cout,))}
        cin = cout
    fc_in = fc_inputs(cfg)
    params["fc"] = {"w": jax.random.normal(keys[-1],
                                           (fc_in, cfg["num_classes"]))
                    * np.sqrt(1.0 / fc_in),
                    "b": jnp.zeros((cfg["num_classes"],))}
    return jax.tree.map(lambda x: x.astype(jnp.float32), params)


def fc_inputs(cfg: dict) -> int:
    side = cfg["image_size"] // (2 ** len(cfg["pool_after"]))
    return side * side * cfg["channels"][-1]


def _split(jax, x):
    """x = hi + lo + O(2^-16 |x|), hi and lo bfloat16 values held in f32."""
    jnp = jax.numpy
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _products(jax, op, x, w, matmul: str):
    """``op(x, w)``, a convolution or a matmul, in float32: at "highest"
    precision, or as ``bf16x3``: three bfloat16 products with float32
    accumulation (hi*hi + hi*lo + lo*hi), forward and backward, which is
    what a TPU's "high" precision computes, written out so that it reads
    the same on any backend (a product of two bfloat16 values is exact
    in float32)."""
    hp = jax.lax.Precision.HIGHEST
    if matmul == "highest":
        return op(x, w, hp)
    if matmul != "bf16x3":
        raise ValueError(f"unknown matmul mode {matmul!r}")

    def three(a, b, f):
        (ah, al), (bh, bl) = _split(jax, a), _split(jax, b)
        return f(ah, bh) + f(ah, bl) + f(al, bh)

    @jax.custom_vjp
    def prod(x, w):
        return three(x, w, lambda a, b: op(a, b, hp))

    def fwd(x, w):
        return prod(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        dx = three(g, w, lambda gg, ww: jax.vjp(
            lambda a: op(a, ww, hp), x)[1](gg)[0])
        dw = three(g, x, lambda gg, xx: jax.vjp(
            lambda b: op(xx, b, hp), w)[1](gg)[0])
        return dx, dw

    prod.defvjp(fwd, bwd)
    return prod(x, w)


def reference_loss(jax, params, images, labels, cfg: dict, matmul: str):
    """Mean softmax cross-entropy of the plain VGG-9 forward pass, in
    float32 with its products computed as ``matmul`` says."""
    jnp = jax.numpy
    lax = jax.lax

    def conv(x, w, precision):
        return lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision)

    def dot(x, w, precision):
        return jnp.dot(x, w, precision=precision)

    x = images.astype(jnp.float32)
    for i in range(len(cfg["channels"])):
        p = params[f"conv{i}"]
        x = _products(jax, conv, x, p["w"], matmul)
        x = x + p["b"]
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
        x = (x - mean) * lax.rsqrt(var + cfg["norm_eps"]) * p["scale"] \
            + p["bias"]
        x = jnp.maximum(x, 0)
        if i in cfg["pool_after"]:
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    logits = _products(jax, dot, x, params["fc"]["w"], matmul) \
        + params["fc"]["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)
    return jnp.mean(nll).astype(jnp.float32)


class Workload(FedCell):
    data_keys = ("images", "labels")
    alter_key = "fc"

    def make_data(self) -> None:
        import jax
        cfg, ds = self.config, self.traffic["dataset"]
        xs, ys = make_images(self.seed, ds["num_samples"],
                             cfg["num_classes"], cfg["image_size"],
                             cfg["in_channels"], ds["noise"],
                             ds["max_shift"])
        self.xs, self.ys = jax.device_put(xs), jax.device_put(ys)
        n_clients = self.traffic["num_clients"]
        rng = np.random.default_rng([self.seed, 2])
        order = rng.permutation(ds["num_samples"])
        self.parts = [np.sort(p) for p in np.array_split(order, n_clients)]
        width = max(len(p) for p in self.parts)
        self.part_idx = jax.numpy.asarray(np.stack(
            [p[np.arange(width) % len(p)] for p in self.parts]), np.int32)
        self.part_sizes = jax.numpy.asarray(
            [len(p) for p in self.parts], np.int32)

    def make_weights(self):
        import jax
        return jax.jit(init_weights, static_argnums=(0, 2))(
            jax, _keys(jax, self.seed, 3), _Frozen(self.config))

    def program_loss(self):
        """The program's loss, one function object per configuration (and
        one more for the half-batch fault, which the loss plants), so that
        the driver's compiled-callable cache hits across runs in one
        process."""
        key = (hash(_Frozen(self.config)), self.fault == "half_batch")
        if key not in _LOSSES:
            _LOSSES[key] = self._make_loss()
        return _LOSSES[key]

    def _make_loss(self):
        from repro.models import cnn
        cfg = self.config
        vcfg = cnn.VGGConfig(channels=tuple(cfg["channels"]),
                             pool_after=tuple(cfg["pool_after"]),
                             num_classes=cfg["num_classes"],
                             image_size=cfg["image_size"],
                             in_channels=cfg["in_channels"])
        if self.fault == "half_batch":
            def loss(p, b):
                half = {k: v[:v.shape[0] // 2] for k, v in b.items()}
                return cnn.classify_loss(p, vcfg, half)
        else:
            def loss(p, b):
                return cnn.classify_loss(p, vcfg, b)
        return loss

    def reference_locals(self, trainable, frozen, idx, control: bool):
        import jax
        fn = _reference_locals_fn(jax, _Frozen(self.config),
                                  _Frozen(self.traffic), control)
        return fn(trainable, self.xs[idx], self.ys[idx])

    def costs(self) -> dict:
        from bench.costs import vgg9
        return {"flops_per_round": vgg9.useful_flops_per_round(
            self.config, self.traffic)}


class _Frozen(dict):
    """A hashable dict, so a configuration can be a static jit argument."""

    def __hash__(self):
        import json
        return hash(json.dumps(self, sort_keys=True))


_REF_FNS: dict = {}
_LOSSES: dict = {}


def _reference_locals_fn(jax, cfg, traffic, control: bool):
    """Jitted (params, images (K,B,...), labels (K,B)) -> (locals, loss).

    The reference computes in float32 at "highest" precision; the control
    computes local training's products as the configuration's
    ``control`` says, one step below the float32 it states."""
    key = (hash(cfg), hash(traffic), control)
    if key in _REF_FNS:
        return _REF_FNS[key]
    jnp = jax.numpy
    matmul = cfg["control"]["matmul"] if control else "highest"
    lr, steps = traffic["lr"], traffic["local_steps"]

    def one_client(p, images, labels):
        losses = []
        for _ in range(steps):
            loss, g = jax.value_and_grad(
                lambda q: reference_loss(jax, q, images, labels, cfg,
                                         matmul))(p)
            losses.append(loss)
            p = jax.tree.map(lambda a, b: a - lr * b.astype(a.dtype), p, g)
        return p, jnp.mean(jnp.stack(losses))

    @jax.jit
    def fn(p, images, labels):
        locals_, losses = jax.vmap(one_client, in_axes=(None, 0, 0))(
            p, images, labels)
        return locals_, jnp.mean(losses)

    _REF_FNS[key] = fn
    return fn
