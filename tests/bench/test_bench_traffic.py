"""The open-loop generator: the same schedule from the same seed, the
same work in another order from another, clipped lengths, exact rate."""
import json

import numpy as np
import pytest

from bench import spec
from bench.traffic import open_loop


# a bursty mix: Gamma inter-arrivals with CV 2, short requests
BURST = {"generator": "open_loop", "arrivals": {"process": "gamma",
                                                "cv": 2.0},
         "prompt_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                           "min": 16, "max": 512},
         "output_tokens": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                           "min": 8, "max": 256}}


def _mix(name):
    return json.loads(json.dumps(BURST)) if name == "burst" \
        else spec.load_mix(name)


@pytest.fixture(params=["chat", "burst"])
def mix(request):
    return _mix(request.param)


def _stream(s, mix):
    """Due times of the requests after the mix's backlog."""
    return s.due_s[mix["arrivals"].get("backlog", 0):]


def test_same_seed_same_schedule(mix):
    a = open_loop.generate(mix, 5.0, 60.0, 2 ** 31 + 12345)
    b = open_loop.generate(mix, 5.0, 60.0, 2 ** 31 + 12345)
    for x, y in zip((a.due_s, a.prompt_len, a.output_len),
                    (b.due_s, b.prompt_len, b.output_len)):
        np.testing.assert_array_equal(x, y)


def test_other_seed_same_work_other_order(mix):
    a = open_loop.generate(mix, 5.0, 60.0, 1)
    b = open_loop.generate(mix, 5.0, 60.0, 2)
    assert not np.array_equal(a.prompt_len, b.prompt_len)
    # each block of the mix's size (all 300 requests without one) holds
    # the same lengths, permuted; the horizon cuts a few of the last
    k = mix.get("block", 300)
    one = sorted(open_loop._lengths(mix["prompt_tokens"], k))
    for s in (a, b):
        assert len(s) >= 295
        for i in range(0, len(s), k):
            left = list(one)
            for n in s.prompt_len[i:i + k]:
                left.remove(n)
            assert len(left) == k - len(s.prompt_len[i:i + k])
    # and every full block of arrivals spans the same time
    g = mix["arrivals"].get("block")
    if g:
        spans = [np.diff(_stream(s, mix))[i:i + g].sum() for s in (a, b)
                 for i in range(g - 1, len(_stream(s, mix)) - g, g)]
        assert np.ptp(spans) < 1e-6


def test_lengths_respect_clips_and_median(mix):
    s = open_loop.generate(mix, 20.0, 200.0, 3)
    for key, got in (("prompt_tokens", s.prompt_len),
                     ("output_tokens", s.output_len)):
        spec_ = mix[key]
        assert got.min() >= spec_["min"] and got.max() <= spec_["max"]
        # the median is one of the two quantiles of a block nearest 1/2,
        # which lie on either side of the distribution's median
        k = mix.get("block", len(s))
        one = np.sort(open_loop._lengths(spec_, k))
        lo, hi = one[(k - 1) // 2], one[k // 2]
        assert lo <= spec_["median"] + 1 and hi >= spec_["median"] - 1
        assert lo <= np.median(got) <= hi


def test_mean_rate_is_exact(mix):
    rate = 7.0
    s = open_loop.generate(mix, rate, 1000.0, 4)
    n = int(np.ceil(rate * 1000.0))
    gaps = np.diff(_stream(s, mix))
    assert s.due_s[0] == 0.0
    assert len(s) >= 0.97 * n
    assert np.mean(gaps) == pytest.approx(1 / rate, rel=0.05)


def test_burst_arrivals_are_burstier_than_poisson():
    def cv(name):
        mix = _mix(name)
        g = np.diff(_stream(open_loop.generate(mix, 10.0, 500.0, 5), mix))
        return g.std() / g.mean()
    assert cv("chat") == pytest.approx(1.0, abs=0.15)
    assert cv("burst") == pytest.approx(2.0, abs=0.4)


def test_unknown_arrival_process_is_refused():
    mix = json.loads(json.dumps(spec.load_mix("chat")))
    mix["arrivals"]["process"] = "uniform"
    with pytest.raises(ValueError):
        open_loop.generate(mix, 1.0, 10.0, 0)


def test_backlog_is_due_at_once_with_the_same_requests_for_every_seed():
    mix = spec.load_mix("chat")
    k = mix["arrivals"]["backlog"]
    assert k == mix["block"]
    a, b = (open_loop.generate(mix, 1.0, 120.0, s) for s in (6, 2 ** 31 + 6))
    for s in (a, b):
        assert np.all(s.due_s[:k] == 0.0) and np.all(np.diff(s.due_s[k:]) > 0)
        assert len(s) == k + int(np.ceil(120.0))
    # the same requests: prompt and output lengths paired alike
    x, y = ({(int(p), int(o)) for p, o in zip(s.prompt_len[:k],
                                              s.output_len[:k])}
            for s in (a, b))
    assert x == y and len(x) > k // 2
    assert not np.array_equal(a.prompt_len[:k], b.prompt_len[:k])


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 7])
def test_strata_put_one_prompt_of_each_band_in_every_run(seed):
    mix = spec.load_mix("chat")
    k, m = mix["block"], mix["strata"]
    bands = np.sort(open_loop._lengths(mix["prompt_tokens"], k)).reshape(
        m, k // m)
    s = open_loop.generate(mix, 1.0, 300.0, seed)
    for i in range(0, len(s) - m + 1, m):
        run = s.prompt_len[i:i + m]
        assert sorted(int(np.searchsorted(bands[:, 0], n, "right")) - 1
                      for n in run) == list(range(m))
