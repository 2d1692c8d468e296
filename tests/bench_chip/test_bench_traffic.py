"""The open-loop schedule: the same work at the same moments for every
seed, at the mean rate; the seed draws the images."""
import chiptiny  # noqa: F401
import numpy as np
import pytest
from chipbench import spec, traffic

MIX = {"rate_per_s": 5.0, "images_min": 1, "images_max": 9}
BIG = 2**31 + 12345


def test_same_seed_same_schedule():
    a, b = traffic.schedule(MIX, 40.0), traffic.schedule(MIX, 40.0)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_seeds_differ_in_order_not_in_work():
    """Every seed gets the same schedule, sizes in one shuffled order; the
    seed draws the images (and which requests are checked)."""
    due, sizes = traffic.schedule(MIX, 40.0)
    assert len(due) == 200 and np.all(np.diff(due) > 0)
    assert 0 < due[0] and due[-1] < 40.0
    assert sizes.min() == 1 and sizes.max() == 9
    assert np.bincount(sizes)[1:].tolist() == [23] * 2 + [22] * 7
    assert np.any(np.diff(sizes) < 0)
    kind = spec.kind_module("serve_open_loop")
    a, b = (kind.request_images(s, sizes[:3], (4, 4, 3)) for s in (1, BIG))
    assert [x.shape for x in a] == [x.shape for x in b]
    assert not np.array_equal(a[0], b[0])


def test_gaps_look_poisson():
    due, _ = traffic.schedule({**MIX, "rate_per_s": 50.0}, 40.0)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / 50, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


def test_sample_is_seeded_and_keeps_the_must():
    a = traffic.sample(100, 10, 5, must=[99])
    assert a == traffic.sample(100, 10, 5, must=[99]) and 99 in a
    assert a != traffic.sample(100, 10, 6, must=[99])
    assert len(a) in (10, 11)
    assert traffic.offered_images_per_s(MIX) == pytest.approx(25.0)
