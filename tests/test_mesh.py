"""Mesh layouts follow the device count (no devices are touched here)."""

import pytest

from repro.launch.mesh import _layout


@pytest.mark.parametrize("n, multi_pod, want", [
    (256, False, (1, 16, 16)),          # one 16 x 16 pod
    (512, True, (2, 16, 16)),           # two pods
    (4, False, (1, 1, 4)),              # one 2 x 2 host: a 4-wide group
    (1, False, (1, 1, 1)),
    (64, False, (1, 4, 16)),
])
def test_layout_takes_the_device_count(n, multi_pod, want):
    assert _layout(n, None, multi_pod) == want


def test_layout_refuses_a_count_that_does_not_tile():
    with pytest.raises(ValueError):
        _layout(24, None, False)
