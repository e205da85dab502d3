import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_like_numpy():
    np = pytest.importorskip("numpy")
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 11.0]
    for q in (0, 10, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_median_of_even_and_odd_counts():
    assert stats.median([1, 2, 3, 4]) == 2.5
    assert stats.median([4, 1, 3]) == 3


def test_sample_counts_qualify_a_tail():
    assert stats.samples_beyond(200, 95) == 10   # a p95 needs 200 samples
    assert stats.samples_beyond(100, 95) == 5
    s = stats.summary(list(range(1, 201)), 95)
    assert s["n"] == 200 and s["beyond"] == 10 and s["max"] == 200
    assert s["p95"] == pytest.approx(190.05)


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1], 101)
