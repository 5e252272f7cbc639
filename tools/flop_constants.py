"""FLOP-accounting constants shared by the perf tools — import-free,
so log parsers never drag jax in.

ResNet-50 training cost in 2xMAC FLOPs (the convention of the chips'
published peaks): forward = 4.09 GMAC = 8.2 GF @ 224x224, x ~3 for
fwd+bwd.  The shape-by-shape derivation lives in tools/conv_ladder.py
and is pinned by tests/test_conv_ladder.py.
"""

TRAIN_GFLOP_PER_IMAGE = 24.6

#: published peak of ONE chip in bf16 TFLOP/s (2xMAC convention), keyed
#: by ``jax.devices()[0].device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
}


def peak_bf16_tflops(device_kind: str) -> float:
    """The table's peak for ``device_kind``; an unknown device is an
    error, never a default (a utilization against the wrong peak is
    worse than none)."""
    if device_kind not in PEAK_BF16_TFLOPS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add "
            "it to tools/flop_constants.py with its source "
            f"(known: {sorted(PEAK_BF16_TFLOPS)})")
    return PEAK_BF16_TFLOPS[device_kind]
