"""Bytes of the jacobi3d kernel calls, against hand counts."""
import pytest

from bench import kernel_bytes as kb


def test_slab_planes():
    assert kb.slab_planes(512, 512, 512, 4) == 1        # 1 MiB planes
    assert kb.slab_planes(64, 64, 64, 4) == 8           # capped at 8
    assert kb.slab_planes(12, 256, 256, 4) == 4         # largest divisor <= 4


def test_sweep_bytes_512_cube():
    # x, b read and new x written: 3 * 512^3 f32; six 512^2 faces read;
    # 512 slabs of one (8,128) f32 partial tile
    want = 4 * 3 * 512 ** 3 + 4 * 6 * 512 ** 2 + 512 * 4096
    assert kb.call_bytes("sweep", (512, 512, 512)) == want


def test_residual_bytes_block():
    # a 512x512x1024 block: x and b read; faces 2*(512*1024 + 512*1024 +
    # 512*512); planes of 2 MiB, so one plane per slab
    cells = 512 * 512 * 1024
    faces = 2 * (512 * 1024 + 512 * 1024 + 512 * 512)
    want = 4 * (2 * cells + faces) + 512 * 4096
    assert kb.call_bytes("residual", (512, 512, 1024)) == want


def test_small_block_partials():
    # 16^3: 8 planes per slab, 2 slabs
    assert kb.call_bytes("sweep", (16, 16, 16)) == (
        4 * (3 * 16 ** 3 + 6 * 16 ** 2) + 2 * 4096)


def test_block_shape_and_calls():
    assert kb.block_shape(1024, (2, 2)) == (512, 512, 1024)
    assert kb.block_shape(512, (1, 1)) == (512, 512, 512)
    assert kb.calls_per_solve("nonblocking", 62) == {"sweep": 62,
                                                      "residual": 0}
    assert kb.calls_per_solve("blocking", 47) == {"sweep": 47,
                                                   "residual": 47}
    assert kb.calls_per_solve("nonblocking", 50, 3) == {"sweep": 50,
                                                         "residual": 3}


def test_unknown_kind():
    with pytest.raises(ValueError):
        kb.call_bytes("gather", (8, 8, 8))
