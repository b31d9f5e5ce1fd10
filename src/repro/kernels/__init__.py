"""Pallas TPU kernels (validated in interpret mode on CPU; Mosaic on TPU).

    fused_pipeline    — the compute backend's one data-path kernel:
                        filter → project → segment fold in ONE launch per
                        morsel (device-resident bit-planes)
    flash_attention   — causal GQA prefill attention
    decode_attention  — split-K single-token decode (seq-shardable)
    ssd_scan          — Mamba2 SSD chunk scan
    mlstm_chunk       — xLSTM chunkwise-parallel mLSTM
"""

from repro.kernels import ops, ref
from repro.kernels.ops import (
    decode_attention,
    flash_attention,
    fused_chain_tiles,
    mlstm_chunk,
    ssd_scan,
)

__all__ = [
    "ops",
    "ref",
    "decode_attention",
    "fused_chain_tiles",
    "flash_attention",
    "mlstm_chunk",
    "ssd_scan",
]
