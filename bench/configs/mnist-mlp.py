"""Plain model of the `mnist-mlp` configuration: a 784-200-100-10 MLP.

Dense layers with ReLU between them and logits out.  Weights are
N(0, 2 / d_in) (He), biases zero; the key splits into one key per layer,
and each layer draws its weight from the first half of its own split.
`macs_per_row` and `n_params` count its work and its weights for
`flops.py`.
"""
import jax
import jax.numpy as jnp


def dims(config):
    return [config["model"]["input_dim"], *config["model"]["hidden"],
            config["model"]["n_classes"]]


def init(key, config):
    d = dims(config)
    keys = jax.random.split(key, len(d) - 1)
    params = {}
    for i in range(len(d) - 1):
        wk, _ = jax.random.split(keys[i])
        params[f"layer{i}"] = {
            "w": jax.random.normal(wk, (d[i], d[i + 1]), jnp.float32)
            * (2.0 / d[i]) ** 0.5,
            "b": jnp.zeros((d[i + 1],), jnp.float32)}
    return params


def apply(params, x, config):
    n = len(dims(config)) - 1
    h = x.reshape((x.shape[0], -1))
    for i in range(n):
        h = h @ params[f"layer{i}"]["w"] + params[f"layer{i}"]["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def macs_per_row(config):
    """Multiply-adds of one forward pass of one input row."""
    d = dims(config)
    return sum(a * b for a, b in zip(d, d[1:]))


def n_params(config):
    """Weights and biases: D, the length of one upload."""
    d = dims(config)
    return sum(a * b + b for a, b in zip(d, d[1:]))
