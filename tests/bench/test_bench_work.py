"""Work counts and peaks of the benchmark, against hand-computed values."""

import pytest


def test_lloyd_step_kddcup99(bench_path):
    from lib import work
    flops, nbytes = work.lloyd_step(4_898_431, 10, 37)
    assert flops == 3_624_838_940          # 2·N·K·d
    assert nbytes == 744_562_992           # 4·(N·d + K·d + N)


def test_lloyd_step_ivf4096(bench_path):
    from lib import work
    flops, nbytes = work.lloyd_step(1_048_576, 4096, 128)
    assert flops == 2 ** 40                # 2·2^20·2^12·2^7
    assert nbytes == 543_162_368           # 4·(2^27 + 2^19 + 2^20)


def test_assign_call_counts_rows_once(bench_path):
    from lib import work
    assert work.assign_call(16_384, 4096, 128) == (2.0 * 16_384 * 4096 * 128,
                                                   4.0 * 16_384 * 128)


def test_least_time_takes_the_binding_bound(bench_path):
    from lib import peaks, work
    v5e = peaks.peaks_for("TPU v5 lite")
    kdd = work.lloyd_step(4_898_431, 10, 37)
    ivf = work.lloyd_step(1_048_576, 4096, 128)
    assert work.bound(*kdd, v5e) == "memory"
    assert work.least_time(*kdd, v5e) == pytest.approx(744_562_992 / 819e9)
    assert work.bound(*ivf, v5e) == "compute"
    assert work.least_time(*ivf, v5e) == pytest.approx(2 ** 40 / 197e12)


def test_unknown_device_kind_is_an_error(bench_path):
    from lib import peaks
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99")
