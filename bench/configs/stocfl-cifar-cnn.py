"""Plain reference of the CIFAR-10 CNN (StoCFL section 4.2): conv3x3(32)
-> ReLU -> maxpool2 -> conv3x3(64) -> ReLU -> maxpool2 -> FC 128 ReLU ->
FC 10, cross-entropy, in jax.numpy and lax with no kernel, cache or
batching, and the weights' distributions the program's ``simple.init``
uses (He-scaled normal convolutions, normal / sqrt(fan_in) dense layers,
zero biases).

``program_task`` and ``program_loss`` build the system under test from
the same sizes; the reference functions import nothing of the program."""
import jax
import jax.numpy as jnp


def _flat(model):
    h, w, _ = model["input_shape"]
    return (int(h) // 4) * (int(w) // 4) * int(model["conv_channels"][1])


def init_params(key, model):
    """Initial weights ω₀ from a key, as the program's parameter tree."""
    c_in = int(model["input_shape"][-1])
    c1, c2 = (int(c) for c in model["conv_channels"])
    kk, fc, nc = int(model["kernel"]), int(model["fc_hidden"]), \
        int(model["n_classes"])
    flat = _flat(model)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "conv1_w": jax.random.normal(k1, (kk, kk, c_in, c1))
        * jnp.sqrt(2.0 / (kk * kk * c_in)),
        "conv1_b": jnp.zeros((c1,)),
        "conv2_w": jax.random.normal(k2, (kk, kk, c1, c2))
        * jnp.sqrt(2.0 / (kk * kk * c1)),
        "conv2_b": jnp.zeros((c2,)),
        "fc1_w": jax.random.normal(k3, (flat, fc)) / jnp.sqrt(float(flat)),
        "fc1_b": jnp.zeros((fc,)),
        "fc2_w": jax.random.normal(k4, (fc, nc)) / jnp.sqrt(float(fc)),
        "fc2_b": jnp.zeros((nc,)),
    }


def _conv(x, w, b):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b


def _pool(x):
    n, h, w, c = x.shape
    return jnp.max(x.reshape(n, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def apply(params, x, model):
    """(B, H, W, C) -> (B, n_classes) logits."""
    h = _pool(jnp.maximum(_conv(x, params["conv1_w"], params["conv1_b"]), 0))
    h = _pool(jnp.maximum(_conv(h, params["conv2_w"], params["conv2_b"]), 0))
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(h @ params["fc1_w"] + params["fc1_b"], 0.0)
    return h @ params["fc2_w"] + params["fc2_b"]


def forward_flops(model):
    """Model FLOPs of one example's forward pass: the two convolutions at
    full and half resolution, then the two dense layers."""
    h, w, c_in = (int(s) for s in model["input_shape"])
    c1, c2 = (int(c) for c in model["conv_channels"])
    kk = int(model["kernel"])
    conv1 = 2 * h * w * c1 * kk * kk * c_in
    conv2 = 2 * (h // 2) * (w // 2) * c2 * kk * kk * c1
    fc = int(model["fc_hidden"])
    return conv1 + conv2 + 2 * _flat(model) * fc + 2 * fc * \
        int(model["n_classes"])


def program_task(model):
    """The program's own model at these sizes (the system under test)."""
    from repro.models import simple
    return simple.TaskConfig(
        "cifar_cnn", "cnn", tuple(int(s) for s in model["input_shape"]),
        int(model["n_classes"]),
        conv_channels=tuple(int(c) for c in model["conv_channels"]),
        fc_hidden=int(model["fc_hidden"]))


def program_loss(model):
    """The program's loss on a client batch at these sizes, as
    ``engine.init`` takes it."""
    from repro.models import simple
    task = program_task(model)
    return lambda params, batch: simple.loss_fn(params, batch, task)
