"""The traffic generator: the same seed gives the same requests, and every
seed gives the same work (class counts, arrival gaps) in another order."""
import collections

import numpy as np
import pytest

import bench_chip_small  # noqa: F401  (puts the checkout on sys.path)
from benchmarks.chip import traffic

BIG_SEED = 2**31 + 12345          # seeds may exceed 32 signed bits


def _chat():
    return traffic.load_mix("chat")


def _summary(reqs):
    return [(r.phase, round(r.due_s, 9), r.prompt.tobytes(), r.output_tokens)
            for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_same_seed_same_requests(seed):
    a = traffic.lm_requests(_chat(), seed, 49152, 30)
    b = traffic.lm_requests(_chat(), seed, 49152, 30)
    assert _summary(a) == _summary(b)


def test_seeds_differ_in_order_only():
    mix = _chat()
    a = traffic.lm_requests(mix, 1, 49152, 30)
    b = traffic.lm_requests(mix, BIG_SEED, 49152, 30)
    assert _summary(a) != _summary(b)
    for phase in traffic.PHASES:
        pa = [r for r in a if r.phase == phase]
        pb = [r for r in b if r.phase == phase]
        assert len(pa) == len(pb)
        assert sorted(len(r.prompt) for r in pa) == \
            sorted(len(r.prompt) for r in pb)
        assert sorted(r.output_tokens for r in pa) == \
            sorted(r.output_tokens for r in pb)
        # every gap between arrivals comes from one fixed set of quantiles
        n = len(pa)
        u = (np.arange(n) + 0.5) / n
        fixed = np.round(-np.log1p(-u) / mix["arrival"]["rate_per_s"], 6)
        for reqs in (pa, pb):
            gaps = np.round(np.diff([r.due_s for r in reqs]), 6)
            assert set(gaps) <= set(fixed)


def test_class_mix_is_exact():
    mix = _chat()
    reqs = [r for r in traffic.lm_requests(mix, 3, 49152, 30)
            if r.phase == "window"]
    n = round(mix["arrival"]["rate_per_s"] * 30)
    assert len(reqs) == n
    for key, get in (("prompt_tokens", lambda r: len(r.prompt)),
                     ("output_tokens", lambda r: r.output_tokens)):
        counts = collections.Counter(get(r) for r in reqs)
        want = dict(zip(mix[key]["values"],
                        traffic.quotas(n, mix[key]["weights"])))
        assert counts == want


def test_window_rate_and_phases():
    mix = _chat()
    reqs = traffic.lm_requests(mix, 5, 49152, 30)
    w0, w1 = traffic.window_bounds(mix, 30)
    win = [r.due_s for r in reqs if r.phase == "window"]
    assert w0 <= min(win) and max(win) < w1
    assert all(a <= b for a, b in zip([r.due_s for r in reqs],
                                      [r.due_s for r in reqs][1:]))
    assert max(r.prompt.max() for r in reqs) < 49152


def test_quotas_largest_remainder():
    assert traffic.quotas(10, [0.4, 0.3, 0.2, 0.1]) == [4, 3, 2, 1]
    assert sum(traffic.quotas(7, [0.4, 0.3, 0.2, 0.1])) == 7
    assert traffic.quotas(3, [1, 1, 1]) == [1, 1, 1]


def test_unknown_arrival_kind_is_refused():
    mix = dict(_chat(), arrival={"kind": "bursty", "rate_per_s": 4.0})
    with pytest.raises(ValueError, match="bursty"):
        traffic.lm_requests(mix, 9, 1000, 30)


def test_stream_mix_is_closed_loop():
    mix = traffic.load_mix("stream")
    assert mix["arrival"] == {"kind": "closed", "clients": 1}
    assert mix["batch"] == 1
