"""The generator: the same seed gives the same requests; every seed gets
the same sizes and gaps in another order."""
import numpy as np

from conftest import MIX
from harness import traffic


def _key(reqs):
    return [(r.rid, r.due, r.output_len, r.prompt.tolist()) for r in reqs]


def test_same_seed_same_requests():
    a = traffic.requests(MIX, 6.0, 10.0, 512, seed=2**31 + 99, stream=1)
    b = traffic.requests(MIX, 6.0, 10.0, 512, seed=2**31 + 99, stream=1)
    assert _key(a) == _key(b)


def test_seeds_share_sizes_not_order():
    a = traffic.requests(MIX, 6.0, 10.0, 512, seed=3, stream=1)
    b = traffic.requests(MIX, 6.0, 10.0, 512, seed=2**33 + 3, stream=1)
    assert len(a) == len(b) == 60
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.output_len for r in a) == sorted(r.output_len for r in b)
    # the same n gaps in another order; the first request is due at 0 and
    # the last one gap before the window's end
    longest = traffic.gaps(6.0, 60).max() * 10.0 / traffic.gaps(6.0, 60).sum()
    for reqs in (a, b):
        span = reqs[-1].due - reqs[0].due
        assert reqs[0].due == 0.0 and 10.0 - longest <= span < 10.0
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]


def test_lengths_follow_the_mix():
    reqs = traffic.requests(MIX, 20.0, 30.0, 512, seed=1, stream=0)
    p = np.array([r.prompt_len for r in reqs])
    o = np.array([r.output_len for r in reqs])
    assert p.min() >= 8 and p.max() <= 96 and abs(np.median(p) - 40) <= 1
    assert o.min() >= 2 and o.max() <= 48 and abs(np.median(o) - 12) <= 1
    assert all(0 <= r.prompt.min() and r.prompt.max() < 512 for r in reqs)
    due = [r.due for r in reqs]
    assert due == sorted(due)
    assert 0.0 <= min(due) and max(due) < 30.0


def test_gaps_are_exponential_quantiles():
    g = traffic.gaps(4.0, 1000)
    assert abs(g.mean() - 0.25) < 0.01
    assert abs(np.median(g) - np.log(2) / 4.0) < 0.01


def test_every_block_holds_one_of_each_stratum():
    """Dealt in blocks of 8: each block of consecutive values holds one
    value from each of the 8 strata of the sorted values, so no stretch
    of the window is all long or all short."""
    rng = np.random.default_rng(2**31 + 7)
    v = traffic.stratified(np.arange(64) * 3, 8, rng)
    assert sorted(v) == list(range(0, 192, 3))
    for b in range(0, 64, 8):
        assert sorted(v[b:b + 8] // 3 // 8) == list(range(8))
    # 20 values in 3 blocks of 7, 7 and 6: block j holds j, j + 3, ...
    v = traffic.stratified(np.arange(20), 8, rng)
    assert sorted(v) == list(range(20))
    blocks, i = [], 0
    while i < 20:
        n = 6 if v[i] % 3 == 2 else 7
        blocks.append(v[i:i + n])
        i += n
    assert all(len(set(b % 3)) == 1 for b in blocks)
    assert sorted(len(b) for b in blocks) == [6, 7, 7]
