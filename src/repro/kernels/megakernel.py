"""Pallas TPU megakernel for one fused edge interval.

``edge_interval_pallas`` executes, per grid step, everything one edge does
between two sync points: its clients' κ₁ local SGD(+momentum) steps AND the
trailing weighted edge mean — one HBM read and one HBM write of the edge's
stacked client rows for the whole interval. The scan-fused superround is
step-major (each of the κ₁ steps streams the full (N, …) state through the
memory hierarchy); here the edge's client block stays VMEM-resident across
every step, so per-interval parameter traffic drops by ~κ₁×.

The kernel is specialized to the repo's canonical flat-row client model —
each client row packs a linear map W ∈ (feat, out), loss = mean squared
error over the local batch — which keeps every step a pair of MXU
contractions and makes the fused interval expressible as a single Pallas
body. General models run the same client-blocked schedule through
``core.hierfavg.build_megakernel_super_round`` (the jnp lowering of this
kernel, XLA-fused); this kernel is the TPU lowering target and the
roofline/bench artifact, validated against ``ref.edge_interval_ref`` at ULP
tolerance in interpret mode (shared step body; only the contraction
lowering differs).

Grid: (num_edges,). VMEM per step (f32): C·(P + κ₁·b·(feat+out)) · 4 bytes
plus the (C, feat, out) gradient/momentum temporaries — e.g. C=8, P=307k,
κ₁=8, b=1: ~12 MB, inside a v5e core's 16 MB budget. The parameter axis
cannot be lane-tiled (each local step needs the client's full W), so the
edge block must fit VMEM whole; the wrapper raises past a documented budget
rather than silently spilling (see docs/performance.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Upper bound on the resident edge block (params + batches + temporaries),
# chosen under the 16 MiB default scoped-VMEM limit of a TPU v5e core.
VMEM_BUDGET_BYTES = 12 << 20
# The compiler's scoped-VMEM limit for the kernel: the budget plus room for
# the pipeline's second buffer of every block.
VMEM_LIMIT_BYTES = 2 * VMEM_BUDGET_BYTES
_EXACT = jax.lax.Precision.HIGHEST  # f32 contractions in full f32 on the MXU


def _interval_steps(params, xin, yin, mu, *, lr: float, momentum: float):
    """The shared fused-interval step body: κ₁ unrolled SGD(+momentum)
    steps for one edge's client block. Called by both the Pallas kernel and
    ``ref.edge_interval_ref`` so interpret-mode parity is bit-exact by
    construction.

    params: (C, feat, out) f32; xin: (C, κ₁, b, feat); yin: (C, κ₁, b, out);
    mu: (C, feat, out) momentum buffer (ignored when momentum == 0).
    Returns (params, mu, losses (C, κ₁) f32).
    """
    k1 = xin.shape[1]
    b, out = yin.shape[2], yin.shape[3]
    losses = []
    for t in range(k1):
        x = xin[:, t]  # (C, b, feat)
        r = jnp.einsum(
            "cbf,cfo->cbo", x, params, precision=_EXACT,
            preferred_element_type=jnp.float32,
        ) - yin[:, t]
        # (C, 1) per step, reduced one axis at a time: Mosaic cannot lower a
        # two-axis reduction down to a 1-D vector
        losses.append(jnp.sum(jnp.sum(jnp.square(r), axis=2), axis=1, keepdims=True) / (b * out))
        grad = (2.0 / (b * out)) * jnp.einsum(
            "cbf,cbo->cfo", x, r, precision=_EXACT,
            preferred_element_type=jnp.float32,
        )
        if momentum != 0.0:
            mu = grad + momentum * mu
            params = params - lr * mu
        else:
            params = params - lr * grad
    return params, mu, jnp.concatenate(losses, axis=1)


def _edge_interval_kernel(
    x_ref, xin_ref, yin_ref, w_ref, mu_ref, o_ref, loss_ref, mu_out_ref,
    *, feat: int, out: int, lr: float, momentum: float,
):
    """One edge: x (C, feat, out) client maps; xin (C, κ₁, b, feat); yin (C,
    κ₁, b, out); w (C, 1) weights; mu (C, feat, out). Writes the post-interval
    edge mean broadcast to members, per-step per-client losses, and the
    stepped momentum buffer."""
    params = x_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    xin = xin_ref[...].astype(jnp.float32)
    yin = yin_ref[...].astype(jnp.float32)
    w = w_ref[:, 0].astype(jnp.float32)  # (C, 1)
    params, mu, losses = _interval_steps(
        params, xin, yin, mu, lr=lr, momentum=momentum
    )
    den = jnp.sum(w)
    mean = jnp.sum(params * w[..., None], axis=0) / den  # (feat, out)
    o_ref[...] = jnp.broadcast_to(mean[None], params.shape).astype(o_ref.dtype)
    loss_ref[:, 0] = losses.astype(jnp.float32)
    mu_out_ref[...] = mu.astype(mu_out_ref.dtype)


def edge_interval_pallas(
    params: jnp.ndarray,
    inputs: jnp.ndarray,
    targets: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    num_edges: int,
    feat: int,
    lr: float,
    momentum: float = 0.0,
    mu: jnp.ndarray = None,
    interpret: bool = False,
):
    """Fused edge interval over stacked flat client rows.

    params: (N, P) with P = feat·out, each row a client's W flattened;
    inputs: (N, κ₁, b, feat); targets: (N, κ₁, b, out); weights: (N,)
    aggregation weights (client order groups edges contiguously, uniform
    tree). mu: optional (N, P) momentum buffer (required iff momentum != 0).

    Returns (aggregated params (N, P) — each row its edge's post-interval
    weighted mean, losses (N, κ₁) f32, mu (N, P)).
    """
    n, p = params.shape
    if n % num_edges:
        raise ValueError(f"N={n} % num_edges={num_edges} != 0")
    if p % feat:
        raise ValueError(f"P={p} not divisible by feat={feat}")
    out = p // feat
    if inputs.shape[0] != n or targets.shape[0] != n or inputs.shape[1] != targets.shape[1]:
        raise ValueError(
            f"batch shapes {inputs.shape}/{targets.shape} incompatible with params {params.shape}"
        )
    k1, b = inputs.shape[1], inputs.shape[2]
    c = n // num_edges
    if momentum != 0.0 and mu is None:
        raise ValueError("momentum != 0 needs a mu buffer")
    if mu is None:
        mu = jnp.zeros_like(params)
    resident = 4 * c * (2 * p + k1 * b * (feat + out)) + 4 * 3 * c * p
    if resident > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"edge block needs ~{resident >> 20} MiB resident, over the "
            f"{VMEM_BUDGET_BYTES >> 20} MiB VMEM budget — shrink "
            f"clients-per-edge, κ₁·b, or the model (see docs/performance.md)"
        )
    # weights and losses carry a unit second-minor dim so that their blocks
    # are whole in their last two dims for any clients-per-edge
    w3 = weights.reshape(n, 1, 1).astype(jnp.float32)
    # each row travels as its (feat, out) map: reshaping a flat row inside the
    # kernel is a lane-to-sublane shape cast Mosaic cannot lower
    maps = (n, feat, out)

    agg, losses, mu = pl.pallas_call(
        functools.partial(
            _edge_interval_kernel, feat=feat, out=out, lr=lr, momentum=momentum
        ),
        grid=(num_edges,),
        in_specs=[
            pl.BlockSpec((c, feat, out), lambda e: (e, 0, 0)),
            pl.BlockSpec((c, k1, b, feat), lambda e: (e, 0, 0, 0)),
            pl.BlockSpec((c, k1, b, out), lambda e: (e, 0, 0, 0)),
            pl.BlockSpec((c, 1, 1), lambda e: (e, 0, 0)),
            pl.BlockSpec((c, feat, out), lambda e: (e, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((c, feat, out), lambda e: (e, 0, 0)),
            pl.BlockSpec((c, 1, k1), lambda e: (e, 0, 0)),
            pl.BlockSpec((c, feat, out), lambda e: (e, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(maps, params.dtype),
            jax.ShapeDtypeStruct((n, 1, k1), jnp.float32),
            jax.ShapeDtypeStruct(maps, params.dtype),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(params.reshape(maps), inputs, targets, w3, mu.reshape(maps))
    return agg.reshape(n, p), losses.reshape(n, k1), mu.reshape(n, p)
