"""Pallas TPU kernels for the paper's aggregation operator — uniform and
ragged group shapes.

Both kernels fuse, per parameter tile, the whole EdgeAggregation/
CloudAggregation body: masked-weighted sum over each client group, safe
divide, broadcast back to the members — one HBM read + one HBM write of the
stacked parameters (the jnp reference does reshape/sum/where in ~4 passes).
On the aggregation-bound cloud hop, this halves HBM traffic.

TPU adaptation: the client axis N is tiny (16-32) and the parameter axis is
huge, so we tile the *parameter* dim into lane-aligned blocks of 128·k and
keep the whole client column resident in VMEM: block (N, bd). The weighted
sum runs in f32 on the VPU/MXU regardless of the storage dtype.

* ``grouped_mean_pallas`` — equal contiguous groups. Reduction in-register
  via a (G, C, bd) reshape; no cross-block communication, perfectly
  parallel grid.
* ``segment_mean_pallas`` — ragged groups. The sorted per-client segment
  ids ride in as one (1, N) VMEM block, fetched once and shared by all grid
  steps (see ``docs/hierarchy.md``; Mosaic loads only scalars from SMEM, so
  the whole vector cannot come from a scalar prefetch). The kernel builds
  the (G, N) membership one-hot from the ids with a broadcasted iota
  compare and reduces with two small matmuls: ``onehot @ (x*w)`` for the
  group sums and ``onehotᵀ @ mean`` for the broadcast-back — MXU work of
  size G×N×bd per tile, still exactly one HBM read + one HBM write of x.
  Zero-survivor groups keep their members' rows via the alive column.

Grid: (ceil(D / bd),). VMEM per step: N·bd·(bytes) ≈ 32·512·4 = 64 KiB
(uniform) plus the (G,N)+(G,bd) one-hot/means scratch for ragged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EXACT = jax.lax.Precision.HIGHEST  # f32 matmuls in full f32 on the MXU, not one bf16 pass


def _agg_kernel(x_ref, w_ref, o_ref, *, num_groups: int):
    """x: (N, bd) tile; w: (N, 1) masked weights; o: (N, bd)."""
    x = x_ref[...].astype(jnp.float32)  # (N, bd)
    w = w_ref[...].astype(jnp.float32)  # (N, 1)
    n, bd = x.shape
    c = n // num_groups
    xg = x.reshape(num_groups, c, bd)
    wg = w.reshape(num_groups, c, 1)
    num = jnp.sum(xg * wg, axis=1, keepdims=True)  # (G,1,bd)
    den = jnp.sum(wg, axis=1, keepdims=True)  # (G,1,1)
    safe = jnp.where(den > 0, den, 1.0)
    mean = num / safe
    out = jnp.where(den > 0, jnp.broadcast_to(mean, xg.shape), xg)
    o_ref[...] = out.reshape(n, bd).astype(o_ref.dtype)


def grouped_mean_pallas(
    x: jnp.ndarray,
    weights: jnp.ndarray,
    num_groups: int,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """x: (N, D) stacked flat params; weights: (N,) already masked.

    Returns the per-group weighted mean broadcast back to members, (N, D).
    D is padded to a block multiple internally.
    """
    n, d = x.shape
    if n % num_groups:
        raise ValueError(f"N={n} % groups={num_groups} != 0")
    pad = (-d) % block_d
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    dp = d + pad
    w2 = weights.reshape(n, 1).astype(jnp.float32)

    out = pl.pallas_call(
        functools.partial(_agg_kernel, num_groups=num_groups),
        grid=(dp // block_d,),
        in_specs=[
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, dp), x.dtype),
        interpret=interpret,
    )(xp, w2)
    return out[:, :d] if pad else out


def _segment_reduce(seg, x, w, num_segments: int):
    """Segment mean of an (N, bd) f32 tile, broadcast back. seg: (1, N) int32
    sorted ids; w: (N, 1) weights. Builds the (G, N) membership one-hot with
    a broadcasted iota compare and reduces with two small MXU matmuls:
    ``onehot @ (x*w)`` for the group sums and ``onehotᵀ @ mean`` back to the
    members; members of dead groups keep their rows."""
    n = x.shape[0]
    gids = jax.lax.broadcasted_iota(jnp.int32, (num_segments, n), 0)
    onehot = (seg == gids).astype(jnp.float32)  # (G, N)
    num = jnp.dot(onehot, x * w, precision=_EXACT, preferred_element_type=jnp.float32)  # (G, bd)
    den = jnp.dot(onehot, w, precision=_EXACT, preferred_element_type=jnp.float32)  # (G, 1)
    mean = num / jnp.where(den > 0, den, 1.0)
    alive = (den > 0).astype(jnp.float32)  # (G, 1)
    # broadcast-back: members of alive groups get the mean, dead groups
    # keep their input rows (onehotᵀ @ alive is each member's liveness)
    back = jnp.dot(onehot.T, mean * alive, precision=_EXACT, preferred_element_type=jnp.float32)
    keep = 1.0 - jnp.dot(onehot.T, alive, precision=_EXACT, preferred_element_type=jnp.float32)
    return back + x * keep


def _segment_kernel(seg_ref, w_ref, x_ref, o_ref, *, num_segments: int):
    """seg: (1, N) int32; w: (N, 1); x: (N, bd) tile; o: (N, bd)."""
    x = x_ref[...].astype(jnp.float32)
    out = _segment_reduce(seg_ref[...], x, w_ref[...].astype(jnp.float32), num_segments)
    o_ref[...] = out.astype(o_ref.dtype)


def _dequant_segment_kernel(seg_ref, w_ref, q_ref, s_ref, o_ref, *, num_segments: int, qblock: int):
    """seg: (1, N) int32; w: (N, 1); q: (N, bd) int8 tile; s: (bd/qblock, N,
    1) f32 scales, one (N, 1) column per quantization block; o: (N, bd) f32.
    Dequantize + one-hot MXU segment reduction in one VMEM residency — the
    int8 payload is the only HBM read of the stacked deltas (~¼ the f32
    bytes)."""
    # lane-aligned qblock-wide slices, each scaled by its own column
    x = jnp.concatenate(
        [q_ref[:, t * qblock:(t + 1) * qblock].astype(jnp.float32) * s_ref[t]
         for t in range(s_ref.shape[0])],
        axis=1,
    )
    out = _segment_reduce(seg_ref[...], x, w_ref[...].astype(jnp.float32), num_segments)
    o_ref[...] = out.astype(o_ref.dtype)


def _segment_specs(n: int):
    """BlockSpecs of the (1, N) ids and (N, 1) weights: whole-array blocks
    with a constant index, fetched into VMEM once for the whole grid."""
    return [pl.BlockSpec((1, n), lambda i: (0, 0)), pl.BlockSpec((n, 1), lambda i: (0, 0))]


def segment_dequant_mean_pallas(
    q: jnp.ndarray,
    scales: jnp.ndarray,
    weights: jnp.ndarray,
    segment_ids,
    num_segments: int,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused dequantize-and-segment-aggregate: consume the compressed link
    payload directly.

    q: (N, D) int8 — each client's delta, quantized row-wise in blocks of
    qblock = D / scales.shape[1] (``quantize.quantize_stacked_pallas`` /
    ``fed.transport.quantize_rows`` layout). scales: (N, D/qblock) f32.
    weights: (N,) already-masked aggregation weights; segment_ids: (N,)
    sorted ints in [0, num_segments).

    Returns the per-segment weighted mean of the dequantized rows broadcast
    back to members, (N, D) f32; zero-weight segments keep their (dequantized)
    rows. One HBM pass over int8 + scales instead of dequantize-then-
    aggregate's extra f32 round trip. ``block_d`` must be a multiple of
    qblock; D is padded to a block_d multiple internally (zero payload +
    zero scale ⇒ exact zeros in the pad lanes).
    """
    n, d = q.shape
    if scales.shape[0] != n or d % scales.shape[1]:
        raise ValueError(f"scales shape {scales.shape} incompatible with q {q.shape}")
    qblock = d // scales.shape[1]
    if block_d % qblock:
        raise ValueError(f"block_d={block_d} must be a multiple of qblock={qblock}")
    seg = jnp.asarray(segment_ids, jnp.int32)
    if seg.shape != (n,):
        raise ValueError(f"segment_ids shape {seg.shape} != ({n},)")
    pad = (-d) % block_d
    qp = jnp.pad(q, ((0, 0), (0, pad))) if pad else q
    sp = jnp.pad(scales, ((0, 0), (0, pad // qblock))) if pad else scales
    dp = d + pad
    # (Dp/qblock, N, 1): each quantization block's scales are one (N, 1)
    # column, so a tile's scales arrive as a leading-dim stack (a (N, bd/qblock)
    # block would not be (8, 128)-aligned)
    st = sp.T[..., None]
    sblock = block_d // qblock

    out = pl.pallas_call(
        functools.partial(_dequant_segment_kernel, num_segments=num_segments, qblock=qblock),
        grid=(dp // block_d,),
        in_specs=_segment_specs(n) + [
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
            pl.BlockSpec((sblock, n, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, dp), jnp.float32),
        interpret=interpret,
    )(seg.reshape(1, n), weights.reshape(n, 1).astype(jnp.float32), qp, st)
    return out[:, :d] if pad else out


def segment_mean_pallas(
    x: jnp.ndarray,
    weights: jnp.ndarray,
    segment_ids,
    num_segments: int,
    *,
    block_d: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged-group aggregation: x (N, D) stacked flat params; weights (N,)
    already masked; segment_ids (N,) sorted ints in [0, num_segments).

    Returns the per-segment weighted mean broadcast back to members, (N, D);
    zero-weight segments keep their rows. D is padded to a block multiple
    internally. The ids travel as one (1, N) VMEM block, resident for every
    grid step.
    """
    n, d = x.shape
    seg = jnp.asarray(segment_ids, jnp.int32)
    if seg.shape != (n,):
        raise ValueError(f"segment_ids shape {seg.shape} != ({n},)")
    pad = (-d) % block_d
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    dp = d + pad

    out = pl.pallas_call(
        functools.partial(_segment_kernel, num_segments=num_segments),
        grid=(dp // block_d,),
        in_specs=_segment_specs(n) + [pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, dp), x.dtype),
        interpret=interpret,
    )(seg.reshape(1, n), weights.reshape(n, 1).astype(jnp.float32), xp)
    return out[:, :d] if pad else out
