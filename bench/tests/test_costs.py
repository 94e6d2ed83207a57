"""Operation and byte counts against shapes counted by hand."""

from bench import costs, spec


def cfg(name):
    return spec.arch_config(spec.load_json(
        spec.ROOT / "bench" / "configs" / f"{name}.json"))


def test_stablelm_decode_flops_one_row():
    # per layer and token: (4 * 2048**2 + 3 * 2048 * 5632) * 2 = 102,760,448;
    # 24 layers, the head 2 * 2048 * 100,352, attention 4 * 32 * 64 * 100 * 24
    want = 24 * 102_760_448 + 411_041_792 + 19_660_800
    assert costs.decode_flops(cfg("stablelm-1.6b"), [100]) == want


def test_stablelm_decode_bytes_one_row():
    # params: 24 * (16,777,216 + 4,096 norms + 34,603,008) + head
    # 205,520,896 + final norm 2,048 + one embedding row 2,048, at 2 bytes;
    # K/V: 24 * (2*32*64*100*2 read + 2*32*64*2 written)
    params = 24 * 51_384_320 + 205_520_896 + 2_048 + 2_048
    want = params * 2 + 24 * (819_200 + 8_192)
    assert costs.decode_bytes(cfg("stablelm-1.6b"), [100]) == want


def test_paged_kernel_counts():
    f, b = costs.paged_decode_kernel(cfg("stablelm-1.6b"), [10, 20])
    assert f == 4 * 32 * 64 * 30
    assert b == 2 * 32 * 64 * 30 * 2 + 2 * 2 * 32 * 64 * 2


def test_least_seconds_names_the_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert costs.least_seconds(1000.0, 10.0, peaks) == (10.0, "compute")
    assert costs.least_seconds(10.0, 1000.0, peaks) == (100.0, "memory")
