"""serve.py's rate over whole bursts, on synthetic client stamps: ticks from
20 to 800 ms apart, a burst's arrivals spread over up to half the spacing,
a window that opens and closes anywhere. The gap that ends a burst is read
from the stamps alone (the widest hole among their silences), so the rate
stays within one tick's tokens of all the tokens over all the time and never
fails for want of bursts.

Run by hand: JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import numpy as np
import pytest

from benchmarks import serve

SLOTS, PER = 16, 4              # a tick hands 64 tokens to the clients
TICK = SLOTS * PER


def stamps(spacing_s, spread, ticks, seed, prefill_every=0, prefill=0.5):
    """Sorted arrival times: tick k's tokens arrive spread over `spread` x
    spacing after k x spacing; with `prefill_every`, every such tick is
    followed by a prefill of `prefill` x spacing that sends one first
    token."""
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for k in range(ticks):
        t += spacing_s
        out += list(t + np.sort(rng.uniform(0, spread * spacing_s, TICK)))
        if prefill_every and k % prefill_every == 0:
            t += spacing_s * prefill
            out.append(t)
    return sorted(out)


@pytest.mark.parametrize("spread", [0.05, 0.25, 0.5])
@pytest.mark.parametrize("spacing_ms", [20, 50, 131, 400, 800])
def test_rate_within_a_ticks_tokens_at_any_tick_length(spacing_ms, spread):
    spacing = spacing_ms / 1000.0
    for seed, prefill_every in ((1, 0), (2, 0), (3, 5), (4, 3)):
        every = stamps(spacing, spread, 60, seed, prefill_every)
        rng = np.random.default_rng(seed)
        # the window opens and closes anywhere, also inside a burst
        t0 = every[0] + rng.uniform(2, 4) * spacing
        t1 = every[-1] - rng.uniform(2, 4) * spacing
        inside = [t for t in every if t0 <= t <= t1]
        ticks = round((t1 - t0) / (every[-1] - every[0]) * 60)
        gap = serve.burst_gap_s(inside, TICK)
        # inside the hole: above a burst's own silences, under a tick's
        assert gap < (1 - spread) * spacing
        rate, bs = serve.rate_over_bursts(inside, gap)
        truth = len(inside) / (t1 - t0)         # all tokens over all time
        assert abs(rate - truth) * (t1 - t0) <= TICK + 1, (seed, rate, truth)
        assert len(bs) >= ticks - 2


def test_the_gap_lies_in_the_widest_hole_of_the_cells_own_silences():
    """The committed cell's shape (my chip runs, PR 27): ticks 131 ms apart,
    a burst written within ~8 ms, neighbours at most ~2 ms apart, and after
    a third of the ticks a prefill whose first token leaves 45-65 ms after
    the burst: two holes, ~2 to 45 ms and 65 to ~120 ms; the first is the
    wider, so a prefill's first token is a burst of its own."""
    rng = np.random.default_rng(7)
    out, t, firsts = [], 0.0, 0
    for k in range(120):
        t += 0.131
        out += list(t + np.sort(rng.uniform(0, 0.008, TICK)))
        if k % 3 == 0:
            t += rng.uniform(0.053, 0.073)
            out.append(t)
            firsts += 1
    out.sort()
    gap = serve.burst_gap_s(out, TICK)
    inside = max(b - a for a, b in zip(out, out[1:]) if b - a < 0.008)
    assert inside < gap < 0.045
    assert len(serve.bursts(out, gap)) == 120 + firsts


def test_a_constant_gap_stops_parting_bursts_once_ticks_come_closer():
    """What PR 27 replaced: 100 ms parts ticks 131 ms apart and merges
    every arrival of ticks 80 ms apart into one burst, and then there is
    no rate to report."""
    slow = stamps(0.131, 0.2, 40, seed=5)
    assert len(serve.bursts(slow, 0.1)) == 40
    fast = stamps(0.080, 0.2, 40, seed=5)
    assert len(serve.bursts(fast, 0.1)) == 1
    with pytest.raises(RuntimeError):
        serve.rate_over_bursts(fast, 0.1)
    rate, bs = serve.rate_over_bursts(fast, serve.burst_gap_s(fast, TICK))
    assert len(bs) == 40
    assert rate == pytest.approx(TICK / 0.080, rel=0.01)


def test_the_cut_never_leaves_fewer_bursts_than_half_the_ticks():
    """Whatever the stamps: arrivals at random have no hole, and the cut
    still leaves at least half as many bursts as the tokens make ticks;
    a handful of tokens is refused by name."""
    jitter = sorted(np.random.default_rng(3).uniform(0, 10, 10_000))
    gap = serve.burst_gap_s(jitter, TICK)
    assert len(serve.bursts(jitter, gap)) >= 10_000 / TICK / 2
    with pytest.raises(RuntimeError, match="too few"):
        serve.burst_gap_s([0.0, 0.1, 0.2], TICK)


def test_bursts_keep_first_count_and_last():
    assert serve.bursts([0.0, 0.01, 0.02, 0.5, 0.51, 1.2], 0.1) \
        == [(0.0, 3, 0.02), (0.5, 2, 0.51), (1.2, 1, 1.2)]
