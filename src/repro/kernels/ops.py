"""Public kernel API; the platform alone picks how the kernels run.

On the CPU backend the kernel bodies run in Pallas interpret mode (Python,
validated against ref.py); on TPU they compile to Mosaic. No other backend
is supported, and nothing can select interpret mode on a TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import hier_aggregate as _ha
from repro.kernels import quantize as _qz
from repro.kernels import rglru_scan as _rg


def use_interpret() -> bool:
    """True on the CPU backend, False on TPU, an error anywhere else."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted on CPU, not on {backend!r}")


def grouped_mean(x, weights, fanouts):
    """The staged weighted mean of a uniform tree over one (N, ...) leaf,
    broadcast back in place: ``fanouts`` bottom-up, one per level up to the
    synced one (``(N // G,)`` is G equal groups)."""
    return _ha.grouped_mean_pallas(x, weights, fanouts, interpret=use_interpret())


def segment_mean(x, weights, segment_ids, num_segments, *, block_d: int = 512):
    """Ragged-group aggregation over sorted segment ids (N,). Dispatches to
    the uniform kernel when the ids form equal contiguous blocks
    (same predicate as the jnp path in core.aggregation)."""
    from repro.core.aggregation import _static_uniform_groups

    if _static_uniform_groups(segment_ids, num_segments) is not None:
        return grouped_mean(x, weights, (x.shape[0] // num_segments,))
    return _ha.segment_mean_pallas(
        x, weights, segment_ids, num_segments, block_d=block_d, interpret=use_interpret()
    )


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128):
    """(BH, S, d) fused attention; falls back to ref for tiny heads."""
    return _fa.flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=use_interpret(),
    )


def rglru_scan(a, b, h0, *, block_d=128):
    return _rg.rglru_scan_pallas(a, b, h0, block_d=block_d, interpret=use_interpret())


def segment_dequant_mean(q, scales, weights, segment_ids, num_segments, *, block_d: int = 512):
    """Fused dequantize-and-segment-aggregate: int8 payload (N, D) +
    per-block scales (N, D/qblock) → per-segment weighted mean of the
    dequantized rows broadcast back, (N, D) f32 — one HBM pass over the
    compressed bytes (the transport layer's decode+aggregate in one)."""
    return _ha.segment_dequant_mean_pallas(
        q, scales, weights, segment_ids, num_segments,
        block_d=block_d, interpret=use_interpret(),
    )


def edge_interval(params, inputs, targets, weights, *, num_edges, feat, lr,
                  momentum=0.0, mu=None):
    """Fused edge interval: κ₁ local SGD(+momentum) steps for every client
    plus the trailing per-edge weighted mean, one kernel launch per cloud-
    free sync — the megakernel's TPU lowering (flat-row linear clients; the
    engine's general-model path is ``core.hierfavg.build_megakernel_super_round``).
    Returns (aggregated params (N, P), losses (N, κ₁), mu (N, P))."""
    from repro.kernels import megakernel as _mk

    return _mk.edge_interval_pallas(
        params, inputs, targets, weights, num_edges=num_edges, feat=feat,
        lr=lr, momentum=momentum, mu=mu, interpret=use_interpret(),
    )


def quantize_int8(x, *, qblock=256):
    return _qz.quantize_pallas(x, qblock=qblock, interpret=use_interpret())


def quantize_stacked(x, *, qblock=256):
    """Stacked (N, D) → (q (N, Dp) int8, scales (N, Dp/qblock) f32), blocks
    per client row — the fused aggregate kernel's payload layout."""
    return _qz.quantize_stacked_pallas(x, qblock=qblock, interpret=use_interpret())


def dequantize_int8(q, s, shape, dtype=jnp.float32):
    return _qz.dequantize_pallas(q, s, shape, dtype, interpret=use_interpret())
