"""RG-LRU linear-recurrence scan as a Pallas TPU kernel.

The recurrence h_t = a_t ⊙ h_{t-1} + b_t is the temporal-mixing core of
RecurrentGemma. GPU implementations lean on warp-level parallel scans; the
TPU-native shape is different: the VPU is a (8,128) vector unit with cheap
per-lane FMA but no cross-lane shuffle-scan, so we go *sequential in time,
wide in channels* — each grid step owns a (block_s, block_d) tile of
(a, b) in VMEM and a (1, block_d) carry in VMEM scratch, and walks
block_s steps with a fori_loop of fused multiply-adds. Channels are
embarrassingly parallel: grid = (B, D/block_d) with the channel axis outer
so each core's carry survives its whole sequence walk.

The sequence axis is NOT gridded (the carry is the loop dependency); a
(8,128)-aligned channel block keeps every FMA fully vectorized. Work is
O(S·D) — same as the jnp associative_scan reference — but one HBM pass
and no log-depth ping-pong buffers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, hT_ref, carry, *, seq_len: int):
    """a,b: (1, S, bd); h0: (1, 1, bd); o: (1, S, bd); hT: (1, 1, bd)."""
    carry[...] = h0_ref[0].astype(jnp.float32)

    def body(t, _):
        a_t = a_ref[0, t].astype(jnp.float32)
        b_t = b_ref[0, t].astype(jnp.float32)
        h = a_t * carry[0] + b_t
        carry[0, :] = h
        o_ref[0, t] = h.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, seq_len, body, 0)
    hT_ref[0] = carry[...].astype(hT_ref.dtype)


def rglru_scan_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    h0: jnp.ndarray,
    *,
    block_d: int = 128,
    interpret: bool = False,
):
    """a, b: (B, S, D) recurrence coefficients; h0: (B, D) initial state.

    Returns (h: (B, S, D) all states, hT: (B, D) final state). D padded to
    a lane multiple internally.
    """
    B, S, D = a.shape
    pad = (-D) % block_d
    if pad:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad)))
    Dp = D + pad
    # h0/hT travel as (B, 1, Dp) so their (1, block_d) blocks span the whole
    # second-minor dim ((1, block_d) blocks of a (B, Dp) array are not
    # (8, 128)-aligned for B > 1)
    h0 = h0.reshape(B, 1, Dp)

    h, hT = pl.pallas_call(
        functools.partial(_rglru_kernel, seq_len=S),
        grid=(B, Dp // block_d),
        in_specs=[
            pl.BlockSpec((1, S, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, S, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_d), lambda i, j: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_d), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Dp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
    hT = hT.reshape(B, Dp)
    if pad:
        return h[..., :D], hT[..., :D]
    return h, hT
