"""The readers of the program's own host spans, on hand-made traces.

The traced span is [0, 1] s and the trace's clock is the benchmark's
(no offset), so a time in nanoseconds is 1e9 times the second it stands
for.  Each reader counts only spans that lie wholly in the traced span:
the profiler drops one that straddles its start or stop."""
import types

import pytest

from bench.lib import harness, spec, trace

HOST, DEV = "/host:CPU", "/device:TPU:0"
MS = 1e6                                      # nanoseconds


def span(name, start_ms, dur_ms, plane=HOST, line="python"):
    return trace.Event(plane, line, name, start_ms * MS, dur_ms * MS)


def module(start_ms, dur_ms, name="jit_prefill(7)"):
    return span(name, start_ms, dur_ms, DEV, trace.MODULES_LINE)


def flash(start_ms, dur_ms=1.0):
    return span("%flash_attention.3 = bf16[1,15,512,64]{3,2,1,0} "
                "custom-call(", start_ms, dur_ms, DEV, trace.OPS_LINE)


def run_with(events, layers=2):
    view = None if events is None else trace.View(events)
    return harness.Run(
        cell=types.SimpleNamespace(config={"num_hidden_layers": layers}),
        seed=0, seconds=1.0, w0=0.0, w1=1.0, records=[], counters={},
        trace=view, trace_window=(0.0, 1.0))


def read(name, run):
    return spec.load_module("metrics", name).read(run)


def edges(name):
    """Two spans straddling the traced span's edges, which no reader
    counts."""
    return [span(name, -100, 300), span(name, 900, 300)]


@pytest.mark.parametrize("metric,name", [
    ("decode.first_token_ms", "decode.first_token"),
    ("kvpages.alloc_wait_ms", "decode.kv_alloc"),
    ("coldstart.apply_ms", "coldstart.A"),
    ("coldstart.compute_wait_ms", "coldstart.E.wait"),
])
def test_mean_span_readers(metric, name):
    ev = edges(name) + [span(name, 100, 100), span(name, 300, 300),
                        span(name + ".other", 100, 500),
                        span(name, 200, 700, plane=DEV)]
    assert read(metric, run_with(ev)) == pytest.approx(200.0)


def test_prefill_wait_subtracts_the_prefill_programs_inside():
    ev = edges("decode.prefill") + [
        # 200 ms holding a 100 ms prefill program (one flash call per
        # layer): 100 ms of wait
        span("decode.prefill", 100, 200), module(150, 100),
        flash(160), flash(200),
        # 50 ms holding a program with one flash call, not a prefill
        span("decode.prefill", 400, 50), module(410, 20), flash(415),
        # 100 ms with a prefill program that runs 20 ms inside it and
        # 40 ms past its end: 80 ms of wait
        span("decode.prefill", 500, 100), module(580, 60),
        flash(590), flash(620),
    ]
    assert read("prefill.wait_ms", run_with(ev)) == \
        pytest.approx((100 + 50 + 80) / 3)


def test_admit_time_per_step():
    ev = [
        span("decode.step", 100, 260), span("decode.admit", 100, 20),
        span("decode.step", 400, 260),                 # no join
        # a step cut by the traced span's end, and its admit inside
        span("decode.step", 900, 300), span("decode.admit", 900, 50),
    ]
    assert read("decode.admit_ms", run_with(ev)) == pytest.approx(10.0)
    no_admit = [span("decode.step", 100, 260)]
    assert read("decode.admit_ms", run_with(no_admit)) == 0.0


NAMES = ("decode.first_token_ms", "kvpages.alloc_wait_ms",
         "prefill.wait_ms", "decode.admit_ms", "coldstart.apply_ms",
         "coldstart.compute_wait_ms")


@pytest.mark.parametrize("metric", NAMES)
def test_none_without_a_trace_or_spans(metric):
    assert read(metric, run_with(None)) is None
    # a program that opens no spans: only the device's work and the
    # spans that straddle the edges
    ev = [module(150, 100), flash(160), flash(200)] + \
        edges("decode.step") + edges("decode.prefill") + \
        edges("coldstart.A")
    assert read(metric, run_with(ev)) is None
