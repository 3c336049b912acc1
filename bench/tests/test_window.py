"""Window arithmetic: tails over all requests, tokens inside the window,
idle share as a union of intervals."""
import types

import pytest

from bench.lib import harness, spec, window


def rec(index, due, first=None, times=(), ok=True):
    r = harness.Record(index=index, n_prompt=8, n_new=len(times), due=due)
    r.t_submit = due
    r.t_first = first
    r.times = list(times)
    r.tokens = [1] * len(times)
    r.ok = ok
    return r


def make_run(records, w0=10.0, w1=20.0):
    return harness.Run(cell=types.SimpleNamespace(config={}), seed=0,
                       seconds=w1 - w0, w0=w0, w1=w1, records=records,
                       counters={})


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert window.percentile(xs, 95) == 95
    assert window.percentile(xs, 100) == 100
    assert window.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        window.percentile([], 95)


def test_ttft_p75_counts_every_request_due_in_the_window():
    """A failed request is a miss at the longest wait; requests due
    outside the window do not count."""
    recs = [rec(i, 10.0 + 0.1 * i, first=10.0 + 0.1 * i + 0.05 * i,
                times=[10.0 + 0.15 * i]) for i in range(16)]
    recs += [rec(16 + i, 11.0, ok=False) for i in range(4)]
    recs.append(rec(30, 25.0, first=99.0, times=[99.0]))   # due after close
    recs.append(rec(31, 5.0, first=30.0, times=[30.0]))    # due before open
    ttft = spec.load_module("metrics", "ttft_p75_s")
    run = make_run(recs)
    # 20 requests due in the window, 4 of them misses: the p75 is the
    # 15th of 20 sorted, 0.05 * 14
    assert ttft.read(run) == pytest.approx(0.70)
    recs[0].ok = False
    assert ttft.read(run) == pytest.approx(0.75)
    recs[1].ok = False        # six misses reach the 75th percentile
    assert ttft.read(run) == pytest.approx(20.0 + harness.WAIT_PAST_CLOSE_S
                                           - 11.0)


def test_tokens_per_s_counts_tokens_inside_the_window():
    recs = [rec(0, 9.0, first=9.5, times=[9.5, 10.0, 10.5, 19.9, 20.0]),
            rec(1, 12.0, first=12.5, times=[12.5, 13.0]),
            rec(2, 12.0, ok=False)]
    tps = spec.load_module("metrics", "tokens_per_s")
    assert tps.read(make_run(recs)) == pytest.approx(5 / 10.0)


def test_itl_p95_over_gaps_ending_in_the_window():
    times = [9.0, 10.1] + [10.1 + 0.01 * i for i in range(1, 100)]
    itl = spec.load_module("metrics", "itl_p95_ms")
    # the 1.1 s gap ends inside the window, so it is the maximum
    assert itl.read(make_run([rec(0, 8.0, 9.0, times)])) == \
        pytest.approx(10.0)
    times[1] = 9.5
    assert itl.read(make_run([rec(0, 8.0, 9.0, times)])) == \
        pytest.approx(10.0)


def test_idle_share_is_one_minus_the_union():
    iv = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert window.union_length(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert window.gaps(iv, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0),
                                          (6.0, 9.0)]
    assert window.merge([(0, 1), (1, 2), (3, 4)]) == [(0, 2), (3, 4)]


def test_decode_steps_and_occupancy():
    """Two requests share the steps whose host times they share."""
    a = rec(0, 10.0, 10.0, [10.0, 10.5, 11.0, 11.5])
    b = rec(1, 10.0, 10.4, [10.4, 11.0, 11.5])
    run = make_run([a, b])
    run.counters["decode_steps"] = 3
    assert run.decode_steps(10.0, 20.0) == [[9], [10, 9], [11, 10]]
    occ = spec.load_module("metrics", "decode.occupancy")
    assert occ.read(run) == pytest.approx(5 / 3)
    step = spec.load_module("metrics", "decode.step_ms")
    assert step.read(run) == pytest.approx(500.0)
