"""The benchmark's yardstick: the published peaks of the chips.

Kept here, under the benchmark's own directory, so that no later PR
that claims a gain can move it.  Copied from ``tools/flop_constants.py``
with memory and bandwidth added; the original is listed under Open
questions in PERF.md for a later PR to delete.  The operation counts
are in ``benchmarks/flops/``, one file a model family.
"""

from __future__ import annotations

#: published peaks of ONE chip, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16
#: (2xMAC convention), 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gb": 16.0,
                    "hbm_gb_per_s": 819.0},
}


def peak(device_kind: str) -> dict:
    """The table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add it "
            f"to benchmarks/peaks.py with its source (known: "
            f"{sorted(PEAKS)})")
    return PEAKS[device_kind]
