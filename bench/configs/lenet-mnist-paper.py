"""Plain reference of the paper's local model: LeNet-5 (arXiv 2409.17754,
Section V-A) for 28x28x1 images, in straightforward jax.numpy.

conv 5x5x6 (valid) -> ReLU -> 2x2 max pool -> conv 5x5x16 (valid) ->
ReLU -> 2x2 max pool -> flatten (4x4x16 = 256, height-width-channel
order) -> fc 120 -> ReLU -> fc 84 -> ReLU -> fc 10.  d = 44,426.

``precision`` and ``dtype`` set how it computes: the reference runs at
float32 with ``HIGHEST`` precision; the control of the output check
runs it in bfloat16.  Nothing here comes from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LAYERS = ("conv1", "conv2", "fc1", "fc2", "fc3")


def shapes(cfg):
    """{layer: (weight shape, bias shape)} from the configuration."""
    fc = cfg["fc"]
    return {
        "conv1": (tuple(cfg["conv1"]), (cfg["conv1"][-1],)),
        "conv2": (tuple(cfg["conv2"]), (cfg["conv2"][-1],)),
        "fc1": ((fc[0], fc[1]), (fc[1],)),
        "fc2": ((fc[1], fc[2]), (fc[2],)),
        "fc3": ((fc[2], fc[3]), (fc[3],)),
    }


def init(cfg, key, n_nodes: int):
    """(n_nodes,)-stacked He-normal weights and zero biases, one model
    per node: ``{layer: {"w": ..., "b": ...}}``."""
    out = {}
    for i, (name, (ws, bs)) in enumerate(shapes(cfg).items()):
        fan_in = 1
        for s in ws[:-1]:
            fan_in *= s
        k = jax.random.fold_in(key, i)
        out[name] = {
            "w": jax.random.normal(k, (n_nodes,) + ws, jnp.float32)
                 * jnp.sqrt(2.0 / fan_in),
            "b": jnp.zeros((n_nodes,) + bs, jnp.float32),
        }
    return out


def _pool2(h):
    b, hh, ww, c = h.shape
    return h.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))


def _conv_valid(x, w, b, precision):
    """Valid convolution as one matrix product over the (kh, kw, cin)
    patches of ``x`` (B, H, W, C): out[p, q] = sum x[p+i, q+j, c] w[i, j, c]."""
    kh, kw, cin, cout = w.shape
    H, W = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    patches = jnp.stack([x[:, i:i + H, j:j + W, :]
                         for i in range(kh) for j in range(kw)], axis=3)
    patches = patches.reshape(x.shape[0], H, W, kh * kw * cin)
    return jnp.dot(patches, w.reshape(kh * kw * cin, cout), precision=precision) + b


def forward(params, images, precision=jax.lax.Precision.HIGHEST):
    """images (B, 28, 28, 1) -> logits (B, 10), for ONE node's params."""
    def dense(x, p):
        return jnp.dot(x, p["w"], precision=precision) + p["b"]

    h = _pool2(jax.nn.relu(_conv_valid(images, params["conv1"]["w"],
                                       params["conv1"]["b"], precision)))
    h = _pool2(jax.nn.relu(_conv_valid(h, params["conv2"]["w"],
                                       params["conv2"]["b"], precision)))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(dense(h, params["fc1"]))
    h = jax.nn.relu(dense(h, params["fc2"]))
    return dense(h, params["fc3"])


def forward_flops(cfg) -> float:
    """FLOPs of one image's forward pass (2 per multiply-add; bias, ReLU
    and pooling left out)."""
    h, w, _ = cfg["image"]
    macs = 0
    for name in ("conv1", "conv2"):
        kh, kw, cin, cout = cfg[name]
        h, w = h - kh + 1, w - kw + 1
        macs += h * w * kh * kw * cin * cout
        h, w = h // 2, w // 2
    fc = cfg["fc"]
    macs += sum(a * b for a, b in zip(fc[:-1], fc[1:]))
    return 2.0 * macs


def param_count(cfg) -> int:
    n = 0
    for ws, bs in shapes(cfg).values():
        size = 1
        for s in ws:
            size *= s
        n += size + bs[0]
    return n
