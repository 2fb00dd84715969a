"""Pallas TPU kernels for the framework's compute hot-spots.

hier_aggregate — the paper's fused grouped weighted-mean aggregation
  (uniform + ragged segment kernels, plus the fused int8
  dequantize-and-segment-aggregate kernel for the compressed transport)
flash_attention — O(S·d)-HBM attention for the 32k prefill / 4k train cells
rglru_scan — RG-LRU linear recurrence, sequential-in-time / wide-in-channels
quantize — blockwise int8 for the compressed HierFAVG link payloads

Each has a pure-jnp oracle in ref.py; ops.py is the public API, which runs
the kernels interpreted on the CPU backend and compiled on TPU.
"""
from repro.kernels import flash_attention, hier_aggregate, ops, quantize, ref, rglru_scan

__all__ = ["flash_attention", "hier_aggregate", "ops", "quantize", "ref", "rglru_scan"]
