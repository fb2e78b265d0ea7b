"""The FLOP shape functions against a hand count at the published sizes."""

import pytest

from benchmarks import flops
from benchmarks.tests import tiny

# by hand, 80x80x4 frames: conv1 19x19x32 of 8x8x4, conv2 8x8x64 of 4x4x32,
# conv3 6x6x64 of 3x3x64
CONV1 = 2 * 19 * 19 * 32 * 256
TRUNK = CONV1 + 2 * 8 * 8 * 64 * 512 + 2 * 6 * 6 * 64 * 576
FEAT = 6 * 6 * 64


def test_trunk():
    assert flops.trunk_flops(80, 80, 4) == (TRUNK, CONV1, FEAT)
    assert TRUNK == 12_763_136


def test_r2d2_step():
    f = tiny.load("configs", "r2d2-atari-1chip")["fields"]
    lstm = 8 * (FEAT + 512) * 512
    heads = (2 * 4 * 512 * 512) + 4 * 512 * 1 + 4 * 512 * 3
    online = 40 * (TRUNK + lstm) + 80 * (3 * (TRUNK + lstm + heads) - CONV1)
    target = 120 * (TRUNK + lstm) + 80 * heads
    want = 64 * (online + target)
    assert flops.r2d2_learn_flops(f, (80, 80), 3) == pytest.approx(want)
    assert want == pytest.approx(634.85e9, rel=0.001)
