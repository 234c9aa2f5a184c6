"""The general traffic generator."""

import collections

import numpy as np

import traffic

POISSON = {"loop": "poisson", "rate_per_s": 50.0, "schedule_seed": 3,
           "sizes": {"min": 1, "max": 8, "weight": "inverse"},
           "pool_images": 64}


def test_same_seed_same_schedule():
    a = traffic.open_schedule(POISSON, 4.0, 2**31 + 9)
    b = traffic.open_schedule(POISSON, 4.0, 2**31 + 9)
    assert a == b


def test_seeds_share_the_multiset_of_gaps_and_sizes():
    a = traffic.open_schedule(POISSON, 4.0, 1)
    b = traffic.open_schedule(POISSON, 4.0, 2)
    assert len(a) == len(b) == 200
    assert sorted(x.images for x in a) == sorted(x.images for x in b)
    assert [x.images for x in a] != [x.images for x in b]
    assert all(0 < x.t <= 4.0 for x in a)
    assert all(0 <= x.pool_offset <= 64 - x.images for x in a)


def test_inverse_sizes_follow_one_over_n():
    sizes = traffic.exact_sizes(POISSON["sizes"], 2718)
    counts = collections.Counter(sizes.tolist())
    h = sum(1.0 / n for n in range(1, 9))
    for n in range(1, 9):
        assert abs(counts[n] - 2718 / (n * h)) <= 1


def test_flush_sizes():
    closed = {"loop": "closed", "clients": 4, "sizes": {"min": 32, "max": 32}}
    assert traffic.flush_sizes(closed, 128) == [32, 64, 96, 128]
    singles = {"loop": "poisson", "sizes": {"min": 1, "max": 1}}
    assert traffic.flush_sizes(singles, 32) == list(range(1, 33))
    assert traffic.flush_sizes(POISSON, 32) == list(range(1, 40))


def test_ticket_slices():
    closed = {"loop": "closed", "clients": 4, "sizes": {"min": 32, "max": 32}}
    assert traffic.ticket_slices(closed, 128) == [
        (0, 32), (32, 32), (64, 32), (96, 32)]
    singles = {"loop": "poisson", "sizes": {"min": 1, "max": 1}}
    assert traffic.ticket_slices(singles, 3) == [(0, 1), (1, 1), (2, 1)]
    assert traffic.ticket_slices(singles, 1) == []
    s = traffic.ticket_slices(POISSON, 10)
    assert (0, 8) in s and (9, 1) in s and (0, 10) not in s
    assert all(o + m <= 10 for o, m in s)


def test_closed_source_is_seeded():
    wl = {"sizes": {"min": 32, "max": 32}, "pool_images": 128}
    a = [traffic.ClosedSource(wl, 7).next() for _ in range(5)]
    b = [traffic.ClosedSource(wl, 7).next() for _ in range(5)]
    assert a == b and all(x.images == 32 for x in a)
    assert np.all([0 <= x.pool_offset <= 96 for x in a])
