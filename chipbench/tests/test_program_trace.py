"""CPU tests of ``chipbench/program_trace.py`` and the readers of the
program's own spans and scopes, on hand-built lists.

Run from the checkout root: ``python -m pytest chipbench/tests``.
"""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness, program_trace, trace_reduce  # noqa: E402

TESTDATA = ROOT / "chipbench" / "testdata"

# two steps on the host: batch [0, 2) with draw [0, 1) and put [1, 2),
# then step [2, 6), callbacks [6, 7); the second step 10 s later
SPANS = [("batch", 0.0, 2.0), ("batch.draw", 0.0, 1.0),
         ("batch.put", 1.0, 2.0), ("step", 2.0, 6.0),
         ("callbacks", 6.0, 7.0),
         ("batch", 10.0, 12.0), ("batch.draw", 10.0, 11.0),
         ("batch.put", 11.0, 12.0), ("step", 12.0, 16.0),
         ("callbacks", 16.0, 17.0)]


def test_innermost_tiles_the_window_by_the_latest_open_span():
    tiles = program_trace.innermost(SPANS[:5], (-1.0, 8.0))
    assert tiles == [("", -1.0, 0.0), ("batch.draw", 0.0, 1.0),
                     ("batch.put", 1.0, 2.0), ("step", 2.0, 6.0),
                     ("callbacks", 6.0, 7.0), ("", 7.0, 8.0)]


def test_idle_by_span_puts_idle_time_under_the_innermost_span():
    # the chip is busy in [5, 6.5) and [15, 16.5): the end of each step
    # call and the start of its callbacks
    busy = [(5.0, 6.5), (15.0, 16.5)]
    idle = program_trace.idle_by_span(busy, SPANS, (0.0, 20.0))
    assert idle == pytest.approx({"batch.draw": 2.0, "batch.put": 2.0,
                                  "step": 6.0, "callbacks": 1.0, "": 6.0})
    assert sum(idle.values()) == pytest.approx(
        20.0 - trace_reduce.length(busy))
    # clipped to a window that starts inside the first step call
    part = program_trace.idle_by_span(busy, SPANS, (3.0, 8.0))
    assert part == pytest.approx({"step": 2.0, "callbacks": 0.5, "": 1.0})


STEP_OPS = [("fusion.1", 0.0, 3.0), ("fusion.2", 3.0, 7.0),
            ("fusion.3", 7.0, 8.0), ("copy.1", 8.0, 9.0),
            ("while.2", 0.0, 3.0),                     # a loop: left out
            ("fusion.1", 10.0, 11.0),                  # another program
            ("fusion.1", 20.0, 23.0)]
STEP_MODULES = [("jit_step", 0.0, 10.0),
                ("jit_convert_element_type", 10.0, 11.0),
                ("jit_step", 20.0, 30.0)]
STEP_SCOPES = {"jit_step": {"fusion.1": "psl.client", "fusion.2": "psl.server",
                            "fusion.3": "psl.update", "copy.1": "",
                            "while.2": "psl.client"},
               "jit_convert_element_type": {"fusion.1": ""}}


def test_scope_time_within_the_named_program():
    dev = trace_reduce.Device(STEP_OPS, STEP_MODULES)
    t = {sc: program_trace.scope_time(dev, STEP_SCOPES, "jit_step", sc)
         for sc in ("psl.client", "psl.server", "psl.update", "")}
    assert t == {"psl.client": 6.0, "psl.server": 4.0, "psl.update": 1.0,
                 "": 1.0}
    assert program_trace.scope_time(dev, STEP_SCOPES, "jit_convert", "") \
        == 1.0
    # a program the scopes do not know: all of its time is under none
    assert program_trace.scope_time(dev, {}, "jit_step", "") == 12.0


def _cpu_profile(tmp_path):
    """A CPU profile of one run of a jitted ``step`` with two scopes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("psl.client"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("psl.server"):
            return (y @ y.T).sum()

    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        step(x).block_until_ready()
    path, = tmp_path.rglob("*.xplane.pb")
    return path


def test_scopes_read_from_a_cpu_profile(tmp_path):
    scopes = program_trace.hlo_scopes(_cpu_profile(tmp_path).read_bytes())
    assert {"psl.client", "psl.server"} <= set(scopes["jit_step"].values())


def test_load_scopes_only_for_the_profile_the_trace_came_from(
        tmp_path, monkeypatch):
    path = _cpu_profile(tmp_path / ".traces" / "cell")
    scopes = program_trace.hlo_scopes(path.read_bytes())
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path)
    # the ops of a hand-built chip: instructions of the profiled program
    names = sorted(scopes["jit_step"])
    ops = [(n, 0.1 * i, 0.1 * i + 0.05) for i, n in enumerate(names)]
    trace = trace_reduce.Trace(
        [trace_reduce.Device(ops, [("jit_step", 0.0, 0.1 * len(names))])],
        [], (0.0, 1.0))
    assert program_trace.load_scopes(trace, "jit_step") == scopes
    # an op the profiled program does not have: another program's trace
    other = trace_reduce.Trace(
        [trace_reduce.Device(ops + [("fusion.9999", 0.0, 0.01)],
                             trace.devices[0].modules)], [], (0.0, 1.0))
    assert program_trace.load_scopes(other, "jit_step") is None
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path / "none")
    assert program_trace.load_scopes(trace, "jit_step") is None


def _reader(name):
    return harness.load_module(ROOT / "chipbench" / "metrics"
                               / f"{name}.py", f"test_metric_{name}")


def _recorded():
    return trace_reduce.Trace.from_json(
        json.loads((TESTDATA / "trace_cnn_v5e.json").read_text()))


def test_step_call_reader_means_the_step_span_per_step():
    read = _reader("step_call_ms.train").read
    record = {"kind": "train", "spans": {"batch": 0.5, "step": 1.8},
              "span_counts": {"batch": 200, "step": 200}}
    assert read(record) == pytest.approx(9.0)
    assert read(dict(record, spans={"batch": 0.5})) is None
    assert read({"kind": "serve"}) is None


def test_idle_in_step_call_reader_on_the_recorded_trace():
    read = _reader("idle_in_step_call_share.train").read
    idle = _reader("device_idle_share.train").read
    trace = _recorded()
    record = {"kind": "train", "trace": trace}
    share = read(record)
    # most of the chip's idle time lies inside the step call
    assert 60.0 < share <= idle(record)
    lo, hi = trace.window
    by_span = program_trace.idle_by_span(
        trace_reduce.busy(trace.devices[0], trace.window), trace.host,
        trace.window)
    assert share == pytest.approx(100.0 * by_span["step"] / (hi - lo))
    assert sum(by_span.values()) / (hi - lo) == pytest.approx(
        idle(record) / 100.0)
    # no harness step spans on the host (a serving trace): nothing to read
    assert read(dict(record, trace=trace_reduce.Trace(
        trace.devices, [], trace.window))) is None
    assert read(dict(record, trace=None)) is None
    assert read({"kind": "serve"}) is None


def test_client_reader_per_step_run(tmp_path, monkeypatch):
    read = _reader("client_step_device_ms.train").read
    path = _cpu_profile(tmp_path / ".traces" / "cell")
    scopes = program_trace.hlo_scopes(path.read_bytes())
    client = sorted(n for n, sc in scopes["jit_step"].items()
                    if sc == "psl.client")
    other = sorted(n for n, sc in scopes["jit_step"].items()
                   if sc != "psl.client")
    # two step runs of 10 ms; each runs one client op of 2 ms and one
    # other op of 3 ms
    ops, modules = [], []
    for t in (0.0, 0.1):
        modules.append(("jit_step", t, t + 0.01))
        ops += [(client[0], t, t + 0.002), (other[0], t + 0.002, t + 0.005)]
    record = {"kind": "train", "step_program": "jit_step",
              "trace": trace_reduce.Trace(
                  [trace_reduce.Device(ops, modules)], [], (0.0, 0.2))}
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path)
    assert read(record) == pytest.approx(2.0)
    # the recorded trace: no profile of its own on disk
    recorded = dict(record, trace=_recorded())
    assert read(recorded) is None
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path / "empty")
    assert read(record) is None
    assert read(recorded) is None
    assert read(dict(record, trace=None)) is None
    assert read({"kind": "serve"}) is None
