"""A tiny token language model with a frozen backbone, for the harness's
CPU tests: it defines every optional hook of a configuration module.

The frozen tree holds an embedding (V, d), two dense layers (d, d) and an
output head (d, V); the trained tree holds a rank-r adapter (A (d, r),
B (r, d)) on each dense layer. A layer is ``h + tanh(h W + (h A) B)``.

``apply`` is the plain reference; ``program_apply`` is the system under
test of this fixture, written apart: it folds each adapter into its
layer's weight, ``h (W + A B)``, as a merged-adapter server would."""
import jax
import jax.numpy as jnp


def _dims(model):
    return (int(model["n_classes"]), int(model["d_model"]),
            int(model["layers"]), int(model["rank"]))


def frozen_params(key, model):
    """The backbone, drawn from ``fixed_seeds.frozen``."""
    v, d, n_layers, _ = _dims(model)
    ke, kw, kh = jax.random.split(key, 3)
    return {"embed": jax.random.normal(ke, (v, d)),
            "w": jax.random.normal(kw, (n_layers, d, d)) / jnp.sqrt(float(d)),
            "head": jax.random.normal(kh, (d, v)) / jnp.sqrt(float(d))}


def init_params(key, model):
    """The trained tree ω₀: one adapter per dense layer."""
    _, d, n_layers, r = _dims(model)
    out = {}
    for i, k in enumerate(jax.random.split(key, n_layers)):
        ka, kb = jax.random.split(k)
        out[f"a{i}"] = jax.random.normal(ka, (d, r)) / jnp.sqrt(float(d))
        out[f"b{i}"] = jax.random.normal(kb, (r, d)) / jnp.sqrt(float(r))
    return out


def apply(params, x, model, frozen):
    """(B, T) tokens -> (B, T, V) logits."""
    h = frozen["embed"][x]
    for i in range(_dims(model)[2]):
        h = h + jnp.tanh(h @ frozen["w"][i]
                         + (h @ params[f"a{i}"]) @ params[f"b{i}"])
    return h @ frozen["head"]


def program_apply(params, x, model, frozen):
    """The fixture's system under test: each adapter merged into W."""
    h = jnp.take(frozen["embed"], x, axis=0)
    for i in range(_dims(model)[2]):
        w = frozen["w"][i] + params[f"a{i}"] @ params[f"b{i}"]
        h = h + jnp.tanh(jnp.einsum("btd,de->bte", h, w))
    return jnp.einsum("btd,dv->btv", h, frozen["head"])


def forward_flops(model):
    """Model FLOPs of one sequence's forward pass: per position the two
    dense layers with their adapters and the output head."""
    v, d, n_layers, r = _dims(model)
    t = int(model["input_shape"][0])
    return t * (n_layers * (2 * d * d + 4 * d * r) + 2 * d * v)


def gradient_flops(model):
    """Model FLOPs of one sequence's gradient with the backbone frozen:
    the forward pass; backward, the activation gradients through every
    layer and the head, and the weight gradients of the adapters alone."""
    v, d, n_layers, r = _dims(model)
    t = int(model["input_shape"][0])
    backward = t * (n_layers * (2 * d * d + 4 * d * r + 4 * d * r)
                    + 2 * d * v)
    return forward_flops(model) + backward


def program_init(model, frozen, strategy, params0, clients, ecfg, mesh):
    """The program's initial state. The engine takes a loss of (params,
    batch) alone, so this fixture closes its loss over the frozen tree,
    which is small here."""
    from repro import engine

    def loss(params, batch):
        logits = program_apply(params, batch["x"], model,
                               frozen).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["y"][..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    return engine.init(strategy, loss, params0, clients, ecfg, mesh=mesh,
                       arena=True)
