"""The generator gives the same inputs for a seed, the same work in every
phase for every seed, and Poisson arrival times within a phase."""
import numpy as np
import traffic

MIX = {"kind": "open_loop", "rate_per_s": 5.0,
       "prompt": {"median": 117, "sigma": 0.8, "min": 16, "max": 512},
       "output": {"median": 245, "sigma": 0.8, "min": 8, "max": 896}}
PHASES = (15.0, 51.0, 65.0)


def _summary(reqs):
    return [(r.due_s, len(r.prompt), r.max_new_tokens) for r in reqs]


def _phase(reqs, lo, hi):
    return [r for r in reqs if lo <= r.due_s < hi]


def test_same_seed_same_requests():
    a = traffic.open_loop(MIX, 2**31 + 11, PHASES, 1000)
    b = traffic.open_loop(MIX, 2**31 + 11, PHASES, 1000)
    assert _summary(a) == _summary(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_the_work_of_each_phase():
    a = traffic.open_loop(MIX, 1, PHASES, 50)
    b = traffic.open_loop(MIX, 2, PHASES, 50)
    assert len(a) == len(b) == 75 + 255 + 325
    for lo, hi in ((0, 15), (15, 66), (66, 131)):
        pa, pb = _phase(a, lo, hi), _phase(b, lo, hi)
        assert len(pa) == len(pb) == round(5.0 * (hi - lo))
        assert sorted(len(r.prompt) for r in pa) == \
            sorted(len(r.prompt) for r in pb)
        assert sorted(r.max_new_tokens for r in pa) == \
            sorted(r.max_new_tokens for r in pb)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    lens = sorted(len(r.prompt) for r in a)
    assert lens[0] >= 16 and lens[-1] <= 512
    assert abs(np.median(lens) - 117) <= 3


def test_arrivals_within_a_phase_are_poisson():
    """Gaps between arrivals look exponential with mean 1/rate (the
    coefficient of variation of an exponential is 1), and the lengths are
    not tied to the arrival order."""
    gaps, rho = [], []
    for seed in range(20):
        reqs = _phase(traffic.open_loop(MIX, seed, (1000.0,), 10), 0, 1000)
        due = np.array([r.due_s for r in reqs])
        assert np.all(np.diff(due) >= 0)
        gaps.append(np.diff(due))
        lens = np.array([len(r.prompt) for r in reqs], float)
        rho.append(np.corrcoef(lens[:-1], lens[1:])[0, 1])
    g = np.concatenate(gaps)
    assert abs(g.mean() - 0.2) < 0.01
    assert abs(g.std() / g.mean() - 1.0) < 0.05
    assert abs(np.mean(rho)) < 0.02


def test_long_prompts_can_cluster():
    """No stratification: somewhere in a few seeds, two of the longest
    twentieth of prompts arrive back to back."""
    hits = 0
    for seed in range(10):
        reqs = traffic.open_loop(MIX, seed, (51.0,), 10)
        lens = np.array([len(r.prompt) for r in reqs])
        top = lens >= np.quantile(lens, 0.95)
        hits += int(np.any(top[:-1] & top[1:]))
    assert hits > 0
