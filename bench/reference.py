"""Plain HierFAVG (arXiv 1905.06641, Algorithm 1) in straightforward JAX,
the reference that decides ``correct``. It imports nothing of the program.

Every client starts from the same weights and runs kappa1 local steps of its
own optimizer on its own rows; then each edge replaces its clients' models
with their data-size-weighted mean; every kappa2 edge rounds the cloud does
the same over all clients. Optimizer state stays with its client. The model
comes in as a per-client ``loss(params, batch)``; the rows come from
``data.BatchOrder``, the draw order of the federated batcher.

Clients are held in groups, one group per chip the cell uses, so that a state
one chip cannot hold still fits; edges never straddle groups. The edge mean
runs inside a group; the cloud mean sums every group's partial sums on the
first chip and sends the mean back.

Variants (``variant=``) put a deliberately weaker computation in the
program's place, to show that the comparison catches it:

    "bf16"         the whole computation in bfloat16 (parameters, optimizer
                   state, activations; the loss reduced in float32)
    "high"         float32 with matmuls at ``high`` precision (three bfloat16
                   passes) in place of ``highest``
    "half_batch"   each step's loss over the first half of its rows only
    "no_exchange"  the cloud mean taken within each chip's group only
"""
from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import BatchOrder

VARIANTS = (None, "bf16", "high", "half_batch", "no_exchange")


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _stack_norms(trees_per_group):
    """{leaf path: norm over every group's stacked leaf}."""
    flat = [jax.tree_util.tree_flatten_with_path(t)[0] for t in trees_per_group]
    out = {}
    for i, (path, _) in enumerate(flat[0]):
        sq = sum(float(_norm(f[i][1])) ** 2 for f in flat)
        out[jax.tree_util.keystr(path)] = sq ** 0.5
    return out


def run(
    loss_fn: Callable,
    params0,
    *,
    optimizer: dict,
    traffic: dict,
    data: dict,
    seed: int,
    make_batch: Callable,
    devices: List,
    intervals: int = 3,
    client_block: int = 1,
    variant: Optional[str] = None,
) -> dict:
    """Readings of ``intervals`` cloud intervals (see ``bench.check``)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    dt = jnp.bfloat16 if variant == "bf16" else jnp.float32
    k1, k2 = traffic["kappas"]
    n_edges, per_edge = traffic["num_edges"], traffic["clients_per_edge"]
    n = n_edges * per_edge
    groups = len(devices)
    if n % groups or (n // groups) % per_edge:
        raise ValueError(f"{n} clients in {per_edge}-client edges do not split over {groups} chips")
    g_size = n // groups
    weights = np.asarray([len(p) for p in data["parts"]], np.float32)
    kind = optimizer["kind"]
    lr = optimizer["lr"]
    b1, b2, eps = optimizer.get("b1", 0.9), optimizer.get("b2", 0.999), optimizer.get("eps", 1e-8)

    def client_loss(p, batch):
        if variant == "half_batch":
            batch = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
        return loss_fn(p, batch, dt)

    grad_fn = jax.value_and_grad(client_loss)

    def group_grads(p, batch):
        # client_block clients at a time: a large model's state leaves little
        # room for every client's activations at once
        return jax.lax.map(lambda pb: grad_fn(*pb), (p, batch), batch_size=client_block)

    def local_steps(p, m, v, count, batches):
        """kappa1 steps for one group: (params, opt state, losses (k1, g),
        per-leaf gradient norms of each step)."""

        def step(carry, batch):
            p, m, v, count = carry
            losses, g = group_grads(p, batch)
            count = count + 1
            if kind == "adam":
                m = jax.tree_util.tree_map(lambda m, g: (b1 * m + (1 - b1) * g).astype(dt), m, g)
                v = jax.tree_util.tree_map(lambda v, g: (b2 * v + (1 - b2) * g * g).astype(dt), v, g)
                c1 = 1 - b1 ** count.astype(jnp.float32)
                c2 = 1 - b2 ** count.astype(jnp.float32)
                p = jax.tree_util.tree_map(
                    lambda p, m, v: (p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)).astype(dt), p, m, v
                )
            else:
                p = jax.tree_util.tree_map(lambda p, g: (p - lr * g).astype(dt), p, g)
            gn = jax.tree_util.tree_map(_norm, g)
            return (p, m, v, count), (losses, gn)

        (p, m, v, count), (losses, gnorms) = jax.lax.scan(step, (p, m, v, count), batches)
        return p, m, v, count, losses, gnorms

    def edge_mean(p, w):
        def leaf(x):
            xg = x.reshape((-1, per_edge) + x.shape[1:]).astype(jnp.float32)
            wg = w.reshape((-1, per_edge) + (1,) * (x.ndim - 1))
            mean = jnp.sum(xg * wg, axis=1, keepdims=True) / jnp.sum(wg, axis=1, keepdims=True)
            return jnp.broadcast_to(mean, xg.shape).reshape(x.shape).astype(x.dtype)

        return jax.tree_util.tree_map(leaf, p)

    def partial_sums(p, w):
        return jax.tree_util.tree_map(
            lambda x: jnp.sum(x.astype(jnp.float32) * w.reshape((-1,) + (1,) * (x.ndim - 1)), axis=0), p
        )

    def broadcast(p, total, wsum):
        return jax.tree_util.tree_map(
            lambda x, s: jnp.broadcast_to((s / wsum)[None], x.shape).astype(x.dtype), p, total
        )

    precision = "default" if dt == jnp.bfloat16 else "high" if variant == "high" else "highest"
    with jax.default_matmul_precision(precision):
        steps_fn = jax.jit(local_steps, donate_argnums=(0, 1, 2, 3))
        edge_fn = jax.jit(edge_mean, donate_argnums=0)
        sums_fn = jax.jit(partial_sums)
        bcast_fn = jax.jit(broadcast, donate_argnums=0)
        cast = jax.jit(
            lambda p0: jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x.astype(dt)[None], (g_size,) + x.shape), p0
            )
        )
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

        P, M, V, W = [], [], [], []
        for gi, dev in enumerate(devices):
            p = cast(jax.device_put(params0, dev))
            P.append(p)
            M.append(zeros(p) if kind == "adam" else None)
            V.append(zeros(p) if kind == "adam" else None)
            W.append(jax.device_put(weights[gi * g_size:(gi + 1) * g_size], dev))
        count = [jax.device_put(jnp.zeros([], jnp.int32), dev) for dev in devices]
        order = BatchOrder(data["parts"], traffic["batch_size"], seed)
        out = {"loss": []}
        for interval in range(intervals):
            for r in range(k2):
                rows = np.stack([order.next_rows() for _ in range(k1)])  # (k1, N, b)
                losses = []
                for gi, dev in enumerate(devices):
                    batch = make_batch(data["arrays"], rows[:, gi * g_size:(gi + 1) * g_size])
                    batch = jax.device_put(batch, dev)
                    P[gi], M[gi], V[gi], count[gi], l, gn = steps_fn(P[gi], M[gi], V[gi], count[gi], batch)
                    losses.append(l)
                    if interval == 0 and r == 0:
                        out.setdefault("grad0_parts", []).append(jax.tree_util.tree_map(lambda x: x[0], gn))
                # read after every group's steps are queued, so the chips run together
                out["loss"].append(float(np.mean(np.concatenate([np.asarray(l, np.float64) for l in losses], axis=1))))
                if r < k2 - 1:
                    P = [edge_fn(p, w) for p, w in zip(P, W)]
                elif variant == "no_exchange":
                    P = [bcast_fn(p, sums_fn(p, w), jnp.sum(w)) for p, w in zip(P, W)]
                else:
                    parts = [jax.device_put(sums_fn(p, w), devices[0]) for p, w in zip(P, W)]
                    total = jax.tree_util.tree_map(lambda *xs: sum(xs), *parts)
                    wsum = float(weights.sum())
                    P = [bcast_fn(p, jax.device_put(total, dev), wsum) for p, dev in zip(P, devices)]
            if interval == 0:
                if kind == "adam":
                    out["first"] = _stack_norms(M)
                else:
                    out["first"] = _stack_norms(
                        [jax.tree_util.tree_map(lambda x, x0: x - x0.astype(x.dtype)[None], p, jax.device_put(params0, dev))
                         for p, dev in zip(P, devices)]
                    )
        out["change"] = _stack_norms(
            [jax.tree_util.tree_map(lambda x, x0: x.astype(jnp.float32) - x0[None], p, jax.device_put(params0, dev))
             for p, dev in zip(P, devices)]
        )
    grad0 = out.pop("grad0_parts")
    flat = [jax.tree_util.tree_flatten_with_path(t)[0] for t in grad0]
    out["grad0"] = {
        jax.tree_util.keystr(path): float(sum(float(f[i][1]) ** 2 for f in flat) ** 0.5)
        for i, (path, _) in enumerate(flat[0])
    }
    return out
