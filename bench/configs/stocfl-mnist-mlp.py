"""Plain reference of the MNIST MLP (StoCFL section 4.2): 784 -> 2048 ReLU
-> 10, cross-entropy, written in jax.numpy with no kernel, cache or
batching, and the weights' distributions the program's ``simple.init``
uses (normal / sqrt(fan_in), zero biases).

``program_task`` and ``program_loss`` build the system under test from
the same sizes; the reference functions import nothing of the program."""
import jax
import jax.numpy as jnp


def init_params(key, model):
    """Initial weights ω₀ from a key, as the program's parameter tree."""
    d_in = 1
    for s in model["input_shape"]:
        d_in *= int(s)
    h, c = int(model["hidden"]), int(model["n_classes"])
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (d_in, h)) / jnp.sqrt(float(d_in)),
            "b1": jnp.zeros((h,)),
            "w2": jax.random.normal(k2, (h, c)) / jnp.sqrt(float(h)),
            "b2": jnp.zeros((c,))}


def apply(params, x, model):
    """(B, *input_shape) -> (B, n_classes) logits."""
    x = x.reshape(x.shape[0], -1)
    h = jnp.maximum(x @ params["w1"] + params["b1"], 0.0)
    return h @ params["w2"] + params["b2"]


def forward_flops(model):
    """Model FLOPs of one example's forward pass (two matmuls)."""
    d_in = 1
    for s in model["input_shape"]:
        d_in *= int(s)
    h, c = int(model["hidden"]), int(model["n_classes"])
    return 2 * (d_in * h + h * c)


def program_task(model):
    """The program's own model at these sizes (the system under test)."""
    from repro.models import simple
    return simple.TaskConfig("mnist_mlp", "mlp", tuple(model["input_shape"]),
                             int(model["n_classes"]),
                             hidden=int(model["hidden"]))


def program_loss(model):
    """The program's loss on a client batch at these sizes, as
    ``engine.init`` takes it."""
    from repro.models import simple
    task = program_task(model)
    return lambda params, batch: simple.loss_fn(params, batch, task)
