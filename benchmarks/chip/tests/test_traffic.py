"""The traffic generator: seeds, pools, sessions, offline refill."""
import numpy as np
import pytest

import harness as hz
import traffic as tl

MIXES = ["chat_burst", "sessions", "offline", "long_prompt"]


def _mix(name):
    return hz.load_json("traffic", name + ".json")


def _requests(mix, seed, horizon=60.0):
    t = tl.Traffic(mix, seed, 50280, horizon)
    return t.pop_due(horizon, queued=0)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_others(name):
    mix = _mix(name)
    a, b = _requests(mix, 2**31 + 11), _requests(mix, 2**31 + 11)
    c = _requests(mix, 12)
    assert [(r.due, r.max_new, r.tokens.tolist()) for r in a] == \
        [(r.due, r.max_new, r.tokens.tolist()) for r in b]
    assert [r.tokens.tolist() for r in a] != [r.tokens.tolist() for r in c]


@pytest.mark.parametrize("name", ["chat_burst", "offline", "long_prompt"])
def test_seeds_permute_one_pool_of_sizes(name):
    mix = _mix(name)
    n = 200
    pa = tl.pool(mix["prompt"], n, np.random.default_rng(1))
    pb = tl.pool(mix["prompt"], n, np.random.default_rng(2))
    assert sorted(pa) == sorted(pb) and list(pa) != list(pb)


@pytest.mark.parametrize("name", MIXES)
def test_every_request_fits_the_engine(name):
    mix = _mix(name)
    t = tl.Traffic(mix, 3, 50280, 1.0)
    cap = mix["engine"]["max_len"]
    longest_new = mix["max_new"]["max"]
    assert max(t.prompt_lengths()) + longest_new - 1 <= cap
    rnd = mix.get("prompt", mix.get("user"))["round"]
    assert all(n % rnd == 0 for n in t.prompt_lengths())


def test_every_seed_offers_the_window_the_same_work():
    mix = _mix("chat_burst")
    ramp, secs = 5.0, 30.0

    def window(seed):
        t = tl.Traffic(mix, seed, 50280, 60.0, window=(ramp, secs))
        return [r for r in t.pop_due(60.0) if ramp <= r.due < ramp + secs]
    a, b = window(1), window(2**31 + 1)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_per_s"] * secs)
    assert sorted(len(r.tokens) for r in a) == sorted(len(r.tokens) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    # one schedule for every seed: the same instants, other contents
    assert [r.due for r in a] == [r.due for r in b]
    assert [r.tokens.tolist() for r in a] != [r.tokens.tolist() for r in b]


def test_intensity_inverts_its_cumulative():
    burst = {"period_s": 10.0, "length_s": 2.0, "factor": 3.0}
    it = tl.Intensity(4.0, burst, 3.7)
    assert it.cumulative(50.0) == pytest.approx(200.0)
    for t in (0.5, 4.0, 5.9, 13.2, 47.0):
        assert it.inverse(it.cumulative(t)) == pytest.approx(t, abs=1e-9)


def test_poisson_rate_and_bursts():
    mix = _mix("chat_burst")
    rate = mix["arrivals"]["rate_per_s"]
    reqs = _requests(mix, 5, horizon=200.0)
    assert len(reqs) / 200.0 == pytest.approx(rate, rel=0.1)
    b = mix["arrivals"]["burst"]
    due = np.array([r.due for r in reqs])
    # a 1-second bin that lies inside the 2-second burst of each period
    counts = np.histogram(due % b["period_s"], bins=10,
                          range=(0, b["period_s"]))[0]
    assert counts.max() > 2 * np.median(counts)


def test_sessions_extend_their_history():
    mix = _mix("sessions")
    t = tl.Traffic(mix, 7, 50280, 30.0)
    first = t.pop_due(30.0)
    r = first[0]
    served = np.arange(r.max_new, dtype=np.int32)
    t.on_complete(r, served, r.due + 1.0)
    nxt = [q for q in t.pop_due(1e9) if q.session == r.session]
    assert len(nxt) == 1 and nxt[0].turn == 2
    p = nxt[0].tokens
    assert p[:len(r.tokens)].tolist() == r.tokens.tolist()
    assert p[len(r.tokens):len(r.tokens) + len(served)].tolist() == \
        served.tolist()
    assert r.tokens[:mix["system_prompt"]].tolist() == \
        first[1].tokens[:mix["system_prompt"]].tolist()


def test_offline_refills_to_backlog():
    mix = _mix("offline")
    t = tl.Traffic(mix, 1, 50280, 10.0)
    backlog = mix["arrivals"]["backlog"]
    assert len(t.pop_due(0.0, queued=0)) == backlog
    assert len(t.pop_due(1.0, queued=backlog - 3)) == 3
    t.close()
    assert t.pop_due(2.0, queued=0) == []


def test_describe_reports_lengths_and_rate():
    reqs = _requests(_mix("chat_burst"), 9, horizon=20.0)
    d = tl.describe(reqs, 20.0)
    assert d["requests"] == len(reqs)
    assert d["prompt"]["min"] >= 32 and d["prompt"]["max"] <= 2048


def test_loguniform_lengths_spread_evenly_over_octaves():
    spec = {"dist": "loguniform", "min": 1024, "max": 8192, "round": 32}
    vals = tl.pool(spec, 3000, np.random.default_rng(0))
    assert vals.min() >= 1024 and vals.max() <= 8192
    assert all(v % 32 == 0 for v in vals)
    # rounding up to 32 moves the last ~1/40 of an octave into the next
    octaves = np.histogram(vals, bins=[1024, 2048, 4096, 8193])[0]
    assert np.all(np.abs(octaves - 1000) <= 30), octaves
