"""paper-cifar10-cnn: the source paper's CIFAR-10 experiment (arXiv
1905.06641, Section IV-A) through the program's federated runner.

Sizes are in ``paper-cifar10-cnn.json``. The model is the program's
``repro.models.cnn`` three-block network (``cifar_cnn_apply``) under its
classification loss, trained by plain SGD without momentum; this file builds
that runner, makes the weights from the seed in the program's layout, counts
the model FLOPs, and holds the plain reference model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_batch(arrays, rows):
    return {"inputs": arrays["inputs"][rows], "targets": arrays["targets"][rows]}


def make_runner(sizes, traffic, data, seed, mesh):
    from repro.core import FedTopology, HierFAVGConfig
    from repro.data import FederatedBatcher
    from repro.fed import FederatedRunner, RunnerConfig
    from repro.models import cnn
    from repro.optim import sgd

    batcher = FederatedBatcher(data["arrays"], data["parts"], batch_size=traffic["batch_size"], seed=seed)
    k1, k2 = traffic["kappas"]
    return FederatedRunner(
        loss_fn=cnn.make_cnn_loss_fn(cnn.cifar_cnn_apply),
        optimizer=sgd(sizes["lr"]),
        topology=FedTopology(num_edges=traffic["num_edges"], clients_per_edge=traffic["clients_per_edge"]),
        hier_config=HierFAVGConfig(kappa1=k1, kappa2=k2),
        data_sizes=batcher.data_sizes,
        batcher=batcher,
        runner_config=RunnerConfig(num_rounds=0, engine="superround"),
        mesh=mesh,
    )


def _layer_shapes(sizes):
    """[(name, weight shape)] in the program's key names: c<block><a|b> convs
    (3x3, SAME, each block ends in a 2x2 max pool), then f1..f3."""
    k = sizes["kernel"]
    out = []
    for i, (cin, cout) in enumerate(sizes["conv_channels"]):
        out.append((f"c{i // 2 + 1}{'ab'[i % 2]}", (k, k, cin, cout)))
    for i, (fin, fout) in enumerate(sizes["fc"]):
        out.append((f"f{i + 1}", (fin, fout)))
    return out


def init_params(sizes, seed):
    """Weights from the seed in one jitted call: weights and biases from
    U(-1/sqrt(fan_in), 1/sqrt(fan_in)), PyTorch's default for conv and linear
    layers, which the paper's experiments used."""
    shapes = _layer_shapes(sizes)

    def make(key):
        keys = jax.random.split(key, 2 * len(shapes))
        p = {}
        for i, (name, s) in enumerate(shapes):
            lim = (s[0] if len(s) == 2 else s[0] * s[1] * s[2]) ** -0.5
            p[name + "w"] = jax.random.uniform(keys[2 * i], s, jnp.float32, -lim, lim)
            p[name + "b"] = jax.random.uniform(keys[2 * i + 1], (s[-1],), jnp.float32, -lim, lim)
        return p

    return jax.jit(make)(jax.random.key(seed))


def first_reading(state, params0):
    """Plain SGD keeps no state: its first interval's scaled gradients are
    the parameters' change (the tree, less the initial weights)."""
    return state.params, params0


def model_flops_per_interval(sizes, traffic):
    """2 * multiply-adds of each conv and FC layer for the forward pass; the
    backward pass twice that, except the first conv, whose input gradient
    nothing needs. A SAME conv's taps that fall on the zero padding are not
    counted: along each side of length n a k-wide kernel has k*n - r*(r+1)
    taps inside the image, r = (k - 1) / 2."""
    h, w, _ = sizes["image_shape"]
    k = sizes["kernel"]
    r = (k - 1) // 2
    fwd = []
    for i, (cin, cout) in enumerate(sizes["conv_channels"]):
        fwd.append(2 * (k * h - r * (r + 1)) * (k * w - r * (r + 1)) * cin * cout)
        if i % 2:
            h, w = h // 2, w // 2
    fwd += [2 * fin * fout for fin, fout in sizes["fc"]]
    per_image = 3 * sum(fwd) - fwd[0]
    k1, k2 = traffic["kappas"]
    clients = traffic["num_edges"] * traffic["clients_per_edge"]
    return float(per_image * clients * traffic["batch_size"] * k1 * k2)


# ---------------------------------------------------------------------------
# plain reference model
# ---------------------------------------------------------------------------


def reference_loss(sizes):
    shapes = _layer_shapes(sizes)
    convs = [n for n, s in shapes if len(s) == 4]
    fcs = [n for n, s in shapes if len(s) == 2]

    def loss(p, batch, dt):
        x = batch["inputs"].astype(dt)
        for i, name in enumerate(convs):
            x = jax.lax.conv_general_dilated(
                x, p[name + "w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
            ) + p[name + "b"]
            x = jnp.maximum(x, 0)
            if i % 2:
                b, hh, ww, c = x.shape
                x = x.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
        x = x.reshape(x.shape[0], -1)
        for i, name in enumerate(fcs):
            x = x @ p[name + "w"] + p[name + "b"]
            if i < len(fcs) - 1:
                x = jnp.maximum(x, 0)
        logits = x.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, batch["targets"][:, None], axis=-1)[:, 0]
        return jnp.mean(lse - tgt)

    return loss


def reference(sizes, traffic, data, seed, devices, variant=None):
    from bench import reference as ref

    return ref.run(
        reference_loss(sizes), init_params(sizes, seed),
        optimizer={"kind": "sgd", "lr": sizes["lr"]},
        traffic=traffic, data=data, seed=seed, make_batch=make_batch, devices=devices,
        client_block=10, variant=variant,  # 200 images a block: a small model, many clients
    )
