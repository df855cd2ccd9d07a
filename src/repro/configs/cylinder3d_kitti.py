"""Cylinder3D (Zhu et al., CVPR 2021) as its SemanticKITTI config runs it:
16 point features in, 20 classes, ``init_size`` 32 (encoder 64/128/256/512
after a 32-wide context block).  ``CONFIG_BENCH`` is a quarter of the
width for CPU-sized serving runs."""
from repro.models.cylinder3d import Cylinder3DConfig

CONFIG_1X = Cylinder3DConfig(in_channels=16, num_classes=20, init_size=32)
CONFIG_BENCH = Cylinder3DConfig(in_channels=16, num_classes=20, init_size=8)
