"""Operations a trained sample of ResNet-50 needs; named by a
configuration's ``flops.file``."""


def train_flops_per_sample(**_shapes) -> float:
    """ResNet-50 (3,4,6,3) at 224x224: forward 4.09 GMAC = 8.2 GFLOP
    (2xMAC), x3 for forward + backward = 24.6 GFLOP per image.  The
    shape-by-shape derivation is ``tools/conv_ladder.py``, pinned by
    ``tests/test_conv_ladder.py``."""
    return 24.6e9
