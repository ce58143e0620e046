"""CPU-only tests of the two idle metrics that read the spans inside
`ServeEngine.step()`, on hand-made reduced traces:

    python -m pytest benchmark/tests/test_idle_metrics.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import trace_reduce  # noqa: E402

unattributed = bench_run.load_module(
    "layer_metrics", "idle_unattributed.serve").compute
engine_python = bench_run.load_module(
    "layer_metrics", "idle_engine_python.serve").compute

OUTSIDE = "outside the benchmark's spans"


def _run(idle_by_span, window_s=4.0, busy_s=3.0):
    return {"trace": {"busy_s": busy_s, "window_s": window_s, "ops": {},
                      "module_ms": {}, "idle_by_span": idle_by_span}}


@pytest.mark.parametrize("name", ["bench.step", "serve.step", OUTSIDE])
def test_each_unattributed_name_counts_as_unattributed(name):
    run = _run({name: 0.4, "DevicePut": 0.1})
    assert unattributed(run) == pytest.approx(10.0)
    assert engine_python(run) is None     # no phase span: nothing to read


def test_phase_spans_are_the_engines_python_and_nothing_else_is():
    run = _run({"serve.deliver": 0.2, "serve.admit.claim": 0.1,
                "serve.prefill.stage": 0.06, "serve.step.tail": 0.04,
                "serve.step": 0.02, "bench.step": 0.01, OUTSIDE: 0.01,
                "bench.bookkeeping": 0.05, "bench.submit": 0.03,
                "np.asarray(jax.Array)": 0.08, "DevicePut": 0.07,
                "PjitFunction(convert_element_type)": 0.04})
    assert engine_python(run) == pytest.approx(100 * 0.4 / 4.0)
    assert unattributed(run) == pytest.approx(100 * 0.04 / 4.0)


def test_runtime_names_belong_to_neither():
    run = _run({"np.asarray(jax.Array)": 0.3, "DevicePut": 0.2,
                "PjitFunction(_squeeze)": 0.1, "scatter": 0.1})
    assert unattributed(run) == 0.0
    assert engine_python(run) is None


def test_the_three_parts_sum_to_the_devices_idle_share():
    idle = {"serve.deliver": 0.25, "serve.decode.fetch": 0.05,
            "bench.step": 0.02, "np.asarray(jax.Array)": 0.4,
            "DevicePut": 0.28}
    run = _run(idle, window_s=4.0, busy_s=3.0)
    runtime = 100 * (0.4 + 0.28) / 4.0
    assert unattributed(run) + engine_python(run) + runtime == \
        pytest.approx(trace_reduce.idle_share(run["trace"]))


@pytest.mark.parametrize("run", [{}, {"trace": {}}, {"trace": None}])
def test_no_trace_is_none(run):
    assert unattributed(run) is None
    assert engine_python(run) is None


def test_gaps_take_the_innermost_span_of_the_bench_thread():
    """The accepted reduction names a gap by the shortest event open at
    its midpoint on the thread that carries `bench.*`: a `serve.*`
    annotation nested in `bench.step` takes the gap from it."""

    class Ev:
        def __init__(self, name, start_s, end_s):
            self.name, self.start_ns = name, start_s * 1e9
            self.duration_ns = (end_s - start_s) * 1e9

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    ops = [Ev("fusion", 0.0, 1.0), Ev("fusion", 2.0, 3.0),
           Ev("fusion", 4.0, 5.0), Ev("fusion", 6.0, 7.0)]
    host = [Ev("bench.step", 0.5, 6.5), Ev("serve.step", 0.6, 6.4),
            Ev("serve.deliver", 1.2, 1.8),          # gap 1-2, mid 1.5
            Ev("serve.decode", 4.6, 6.2),           # gap 5-6, mid 5.5
            Ev("serve.decode.fetch", 5.2, 6.1),
            Ev("np.asarray(jax.Array)", 5.3, 6.0)]  # gap 3-4: serve.step
    profile = type("P", (), {"planes": [
        Plane(trace_reduce.DEVICE_PLANE,
              [Line(trace_reduce.OPS_LINE, ops)]),
        Plane("/host:CPU", [Line("other", [Ev("x", 0.0, 7.0)]),
                            Line("python", host)])]})()
    reduced = trace_reduce.reduce(profile)
    assert reduced["idle_by_span"] == pytest.approx(
        {"serve.deliver": 1.0, "serve.step": 1.0,
         "np.asarray(jax.Array)": 1.0})
    run = {"trace": reduced}
    assert engine_python(run) == pytest.approx(100 * 1.0 / 7.0)
    assert unattributed(run) == pytest.approx(100 * 1.0 / 7.0)
