"""Work counts of the benchmarked operations and the chips' peaks.

Each count is the work the *answer* needs, whatever computes it, so that a
later implementation that skips redundant work can never read above its
roofline.

Peaks: Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks", "predict_bytes", "least_seconds"]

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}") from None


def predict_bytes(n_q: int, n_trees: int, n_classes: int) -> int:
    """Bytes an out-of-sample predict must move: per (row, tree) a 4-byte
    leaf id, a 4-byte query weight and the leaf's C 4-byte class sums
    (S = W^T Y depends only on the model, so building it is not the
    batch's work), plus the (n_q, C) answer."""
    return n_q * n_trees * (4 + 4 + 4 * n_classes) + 4 * n_q * n_classes


def least_seconds(device_kind: str, n_bytes: float = 0.0,
                  flops: float = 0.0) -> float:
    """The least time one chip needs for this work: the larger of the
    bytes over HBM bandwidth and the operations over peak."""
    p = peaks(device_kind)
    return max(n_bytes / p["hbm_bytes_per_s"], flops / p["bf16_flops"])
