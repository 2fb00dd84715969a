"""Pallas TPU kernels for the paper's aggregation operator — uniform and
ragged group shapes.

Each kernel fuses, per parameter tile, the whole EdgeAggregation/
CloudAggregation body: masked-weighted sum over each client group, safe
divide, broadcast back to the members — one HBM read + one HBM write of the
stacked parameters (the jnp path reads and writes them three times or more).
The weighted sums run in f32 regardless of the storage dtype.

* ``grouped_mean_pallas`` — a uniform tree, on the leaf's own shape: the
  level syncs' hot path on TPU (``core.aggregation.hierarchical_segment_mean``
  dispatches to it). A leaf is viewed as (N, R, C) — C its last dim, R the
  product of the middle dims, a bitcast under (8, 128) tiling — and tiled
  over R and C in (N, br, bc) blocks of at most 1 MiB, the client axis
  whole, so the (8, 128) tiles hold one client's parameters and are full
  even at N = 4. The masked weights are (N,) scalars in SMEM. Every level
  up to the synced one is a stage within the tile (the cloud sync is one
  pass too), and the output aliases the input.
* ``segment_mean_pallas`` — ragged groups on a flat (N, D) stack. The
  sorted per-client segment ids ride in as one (1, N) VMEM block, fetched
  once and shared by all grid steps (see ``docs/hierarchy.md``; Mosaic
  loads only scalars from SMEM, so the whole vector cannot come from a
  scalar prefetch). The kernel builds the (G, N) membership one-hot from
  the ids with a broadcasted iota compare and reduces with two small
  matmuls: ``onehot @ (x*w)`` for the group sums and ``onehotᵀ @ mean`` for
  the broadcast-back — MXU work of size G×N×bd per tile. Zero-survivor
  groups keep their members' rows via the alive column. Grid:
  (ceil(D / bd),); VMEM per step N·bd·(bytes) plus the (G,N)+(G,bd)
  one-hot/means scratch.
"""
from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EXACT = jax.lax.Precision.HIGHEST  # f32 matmuls in full f32 on the MXU, not one bf16 pass


_BLOCK_BYTES = 1 << 20  # per pipelined buffer (input and output, each double-buffered)
_ROW_LANES = 1024  # lanes of one member row per inner step (3-D view): 8 f32 vregs
_FLAT_LANES = 512  # lanes of one member row per inner step (2-D view)
# scoped VMEM: the four 1 MiB pipelined buffers and one inner step's f32
# rows, with room. Left undeclared, the lm-100m superround compiled for a
# v5e reserves 147 MB more HBM temporaries (PERF.md, Findings)
_VMEM_LIMIT = 16 << 20


@dataclasses.dataclass(frozen=True)
class GroupMeanPlan:
    """How ``grouped_mean_pallas`` tiles one leaf: the (N, R, C) or (N, C)
    view, its block and grid, and the inner steps each block is read in."""

    view: Tuple[int, ...]
    block: Tuple[int, ...]
    grid: Tuple[int, ...]
    step: int  # rows (3-D) or lanes (2-D) of one inner step
    steps: int


def _sublanes(dtype) -> int:
    return 8 * 4 // jnp.dtype(dtype).itemsize  # one (8, 128) f32 tile's rows at this width


def group_mean_plan(shape: Sequence[int], dtype) -> Optional[GroupMeanPlan]:
    """The tiling of a (N, ...) leaf for ``grouped_mean_pallas``, or None
    where it does not tile: rank < 2, not a 16/32-bit float, middle dims
    whose merge would not be a bitcast under (8, 128) tiling, or more
    clients than one minimal block holds.

    A leaf of rank >= 3 is viewed as (N, R, C): C its last dim, R the
    product of the middle dims. Blocks are (N, br, bc), the client axis
    whole, ``br`` a multiple of the sublane tile or all of R, ``bc`` a
    multiple of 128 or all of C, each buffer at most 1 MiB. A rank-2 leaf
    keeps its (N, C) layout in (N, bc) blocks.
    """
    dtype = jnp.dtype(dtype)
    if len(shape) < 2 or not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize not in (2, 4):
        return None
    if len(shape) > 3 and shape[-2] % _sublanes(dtype):
        return None
    n, c = int(shape[0]), int(shape[-1])
    budget = _BLOCK_BYTES // (n * dtype.itemsize)  # elements of one member's slice of a block
    if len(shape) == 2:
        bc = _lanes(c, budget, 16 * _FLAT_LANES)  # at most 16 inner steps a block
        if bc is None:
            return None
        step = next((s for s in (_FLAT_LANES, 256, 128) if bc % s == 0), bc)
        return GroupMeanPlan((n, c), (n, bc), (pl.cdiv(c, bc),), step, bc // step)
    sub = _sublanes(dtype)
    r = int(np.prod(shape[1:-1], dtype=np.int64))
    bc = _lanes(c, budget // min(r, sub), _ROW_LANES)
    if bc is None:
        return None
    if r <= sub:
        return GroupMeanPlan((n, r, c), (n, r, bc), (1, pl.cdiv(c, bc)), r, 1)
    br = sub * min(budget // bc // sub, r // sub)  # a partial last block, never one past R
    return GroupMeanPlan((n, r, c), (n, br, bc), (pl.cdiv(r, br), pl.cdiv(c, bc)), sub, br // sub)


def _lanes(c: int, budget: int, cap: int) -> Optional[int]:
    """A block's last dim: all of C where it fits ``cap`` and the budget,
    else the largest multiple of 128 that does; None where none does."""
    cap = min(budget, cap)
    if c <= cap:
        return c
    return cap // 128 * 128 or None


def _group_mean_kernel(w_ref, x_ref, o_ref, *, fanouts, plan):
    """w: (N,) f32 masked weights in SMEM; x, o: one (N, br, bc) or (N, bc)
    block, the same HBM buffer. Each inner step loads every member's slice
    once, runs every level's stage in f32, and stores each member once."""
    n = x_ref.shape[0]
    w = [w_ref[i] for i in range(n)]
    stages = []  # per level: (members, safe denominator, alive) of each group
    size = 1
    for f in fanouts:
        size *= f
        groups = []
        for g in range(n // size):
            members = range(g * size, (g + 1) * size)
            den = functools.reduce(operator.add, [w[i] for i in members])
            groups.append((members, jnp.where(den > 0, den, 1.0), den > 0))
        stages.append(groups)
    flat = len(plan.view) == 2

    def at(k):
        start = k * plan.step if isinstance(k, int) else pl.multiple_of(k * plan.step, plan.step)
        part = pl.ds(start, plan.step)
        return (part,) if flat else (part, slice(None))

    def body(k, carry):
        idx = at(k)
        rows = [x_ref[(pl.ds(i, 1),) + idx].astype(jnp.float32) for i in range(n)]
        for groups in stages:
            out = list(rows)
            for members, safe, alive in groups:
                num = functools.reduce(operator.add, [rows[i] * w[i] for i in members])
                mean = num / safe
                for i in members:
                    out[i] = jnp.where(alive, mean, rows[i])
            rows = out
        for i in range(n):
            o_ref[(pl.ds(i, 1),) + idx] = rows[i].astype(o_ref.dtype)
        return carry

    if plan.steps == 1:
        body(0, 0)
    else:
        jax.lax.fori_loop(0, plan.steps, body, 0)


def grouped_mean_pallas(
    x: jnp.ndarray,
    weights: jnp.ndarray,
    fanouts: Sequence[int],
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """The staged weighted mean of a uniform tree, broadcast back, in place.

    x: (N, ...) one stacked leaf, clients of a group contiguous; weights:
    (N,) already masked. ``fanouts[t]`` is the children of each level-(t+1)
    node, bottom-up, for every level up to the synced one: ``(C,)`` is an
    edge sync of groups of C clients, ``(C, E)`` the cloud sync of E edges
    of C. Each level averages the previous level's output over its clients
    with the |D_i| weights; a group with no weight keeps its rows — the
    stages of ``core.aggregation.hierarchical_segment_mean``, in f32. One
    read and one write of x: the output aliases it. Raises where
    ``group_mean_plan`` finds no tiling.
    """
    n = x.shape[0]
    fanouts = tuple(int(f) for f in fanouts)
    if n % int(np.prod(fanouts)):
        raise ValueError(f"N={n} is not a multiple of the fan-outs {fanouts}")
    plan = group_mean_plan(x.shape, x.dtype)
    if plan is None:
        raise ValueError(f"a {x.dtype} leaf of shape {x.shape} does not tile")
    if len(plan.view) == 2:
        index = lambda j: (0, j)
    else:
        index = lambda i, j: (0, i, j)
    out = pl.pallas_call(
        functools.partial(_group_mean_kernel, fanouts=fanouts, plan=plan),
        grid=plan.grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), pl.BlockSpec(plan.block, index)],
        out_specs=pl.BlockSpec(plan.block, index),
        out_shape=jax.ShapeDtypeStruct(plan.view, x.dtype),
        input_output_aliases={1: 0},
        name="hier_group_mean",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(weights.astype(jnp.float32), x.reshape(plan.view))
    return out.reshape(x.shape)


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
