"""Work counts against hand counts for both configurations, through the
reference module each names (``bench/references/llama.py``)."""

import pytest

from harness import spec, work
from harness.weights import model_config

PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def _ref(name):
    """The configuration's reference module and its ``arch``."""
    conf = {c["name"]: c for c in spec.benchmark()["configs"]}[name]
    config = spec._json(spec.ROOT / conf["file"])
    ref = spec.reference(config)
    return ref, ref.arch(model_config(config))


@pytest.mark.parametrize("name,weights,kv", [
    # 32 x (2 x 960^2 + 2 x 960 x 320 + 3 x 960 x 2560) + 49152 x 960
    ("smollm-360m", 361_758_720, 23_040),
    # 32 x (2 x 4096^2 + 2 x 4096 x 1024 + 3 x 4096 x 14336) + 49152 x 4096
    ("granite-8b-l32", 7_180_648_448, 73_728),
])
def test_gemm_weights_and_kv_bytes(name, weights, kv):
    ref, arch = _ref(name)
    assert ref.gemm_weights(arch) == weights
    assert ref.kv_bytes_per_token(arch, 32) == kv


def test_v3_step_by_hand():
    """smollm's wq at 64 rows: 2*64*960*960 ops, 960*960 + 4*(960/256)*960
    + 64*960 + 4*64*960 bytes; bound by bytes."""
    ref, arch = _ref("smollm-360m")
    k = n = 960
    ops = 2 * 64 * k * n
    nbytes = k * n + 4 * (k / 256) * n + 64 * k + 4 * 64 * n
    one = max(ops / PEAKS["int8_ops_per_s"], nbytes / PEAKS["hbm_bytes_per_s"])
    assert one == nbytes / PEAKS["hbm_bytes_per_s"]
    total = ref.v3_step(arch, 64, 256, PEAKS)
    per_layer = sum(
        max(2 * 64 * k * n / PEAKS["int8_ops_per_s"],
            (k * n + 4 * (k / 256) * n + 64 * k + 4 * 64 * n) / PEAKS["hbm_bytes_per_s"])
        for k, n in [(960, 960), (960, 320), (960, 320), (960, 960), (960, 2560), (960, 2560), (2560, 960)]
    )
    assert total == pytest.approx(32 * per_layer, rel=1e-12)


def test_v4_step_by_hand():
    """granite: two slots with 64 and 32 packed rows: 96 rows x 8 kv heads
    x 2 x (128 + 4 * 4) bytes per layer, bound by bytes."""
    ref, arch = _ref("granite-8b-l32")
    nbytes = 96 * 8 * 2 * (128 + 16)
    ops = 4.0 * 96 * 32 * 128
    assert ref.v4_step(arch, [64, 32], 32, PEAKS) == pytest.approx(
        32 * max(ops / PEAKS["int8_ops_per_s"], nbytes / PEAKS["hbm_bytes_per_s"]), rel=1e-12)


def test_decode_token_ops_and_packed_len():
    ref, arch = _ref("smollm-360m")
    assert ref.decode_token_ops(arch, 100) == 2.0 * 361_758_720 + 4.0 * 100 * 15 * 64 * 32
    assert [work.packed_len(n, 32) for n in (1, 31, 32, 33, 95)] == [0, 0, 32, 32, 64]
