"""The readers of the program's own spans: on a traced CPU run of
``lubm-mix``, and on hand-built runs whose values are known."""

from __future__ import annotations

from types import SimpleNamespace

import pytest

from benchmarks.chip.metrics import reader
from benchmarks.chip.run import Request, RunData
from repro.obs import Span, Trace

NEW = ("encode_ms_mean", "exec_host_ms_mean", "host_offcpu_share")


def test_traced_run_reports_program_span_metrics(tiny_run):
    res = tiny_run("lubm-mix", trace=True)
    assert res["correct"], res["checks"]
    metrics = res["metrics"]
    assert set(NEW) <= set(metrics)
    assert metrics["encode_ms_mean"]["value"] > 0
    assert metrics["exec_host_ms_mean"]["value"] > 0
    assert 0 <= metrics["host_offcpu_share"]["value"] <= 100


def _span(name, dur, cpu=None, children=(), **meta):
    s = Span(name, 0.0, meta or None)
    s.dur, s.cpu = dur, cpu
    s.children = list(children)
    return s


def _trace(*spans):
    t = Trace()
    t.root.children = list(spans)
    return t


def _request(status=200):
    return Request("Q", 0.0, 0.0, 0.1, status, 1, "", "")


def _run(traces, window):
    return RunData(seconds=10.0, setup_s=1.0, t0=100.0, timeout_s=30.0,
                   window=window,
                   probes=SimpleNamespace(traces=traces))


def test_readers_on_hand_built_run():
    # a solo request: the executor's branch waits 30 ms on the device and
    # compiles for 10 ms; decode is off the CPU for 20 ms
    solo = _trace(
        _span("parse", 0.002, 0.001),
        _span("queue_wait", 0.5),
        _span("execute", 0.100, 0.050, [
            _span("branch", 0.090, 0.040, [
                _span("device_wait", 0.030, 0.0),
                _span("compile", 0.010, 0.010),
                _span("step", 0.0),
                _span("host_ops", 0.020, 0.020)])]),
        _span("decode", 0.050, 0.030, rows=10),
        _span("serialize", 0.030, 0.030),
        _span("write", 0.010, 0.0))
    # a batch member: its execute is the leader's, copied and shared
    member = _trace(
        _span("execute", 0.100, None, shared=True, batch=2, leader="a"),
        _span("decode", 0.010, 0.010),
        _span("serialize", 0.010, 0.010))
    # a coalesced waiter: its execute is two seconds of waiting for the
    # flight it joined, shared (even where the wait carries a CPU figure)
    waiter = _trace(
        _span("execute", 2.0, 0.0, shared=True, coalesced_into="a"),
        _span("decode", 0.010, 0.010),
        _span("serialize", 0.010, 0.010))
    late = _trace(_span("decode", 5.0, 0.0))  # recorded after the window
    run = _run([(101.0, solo), (105.0, member), (106.0, waiter),
                (111.0, late)],
               [_request(), _request(), _request(), _request(503)])
    value = {name: reader(name)(run) for name in NEW}
    assert value["encode_ms_mean"] == pytest.approx(
        (50 + 30 + 10 + 10 + 10 + 10) / 3)
    assert value["exec_host_ms_mean"] == pytest.approx((100 - 30 - 10) / 3)
    # own time of parse, execute, branch, host_ops, decode, serialize
    # (solo) and decode, serialize (member, waiter): wall 182 ms, 41 ms
    # off-CPU
    wall = 2 + (100 - 90) + (90 - 60) + 20 + 50 + 30 + 10 + 10 + 10 + 10
    off = (2 - 1) + 0 + (30 - 10) + 0 + (50 - 30) + 0 + 0 + 0 + 0 + 0
    assert value["host_offcpu_share"] == pytest.approx(100 * off / wall)


def test_readers_find_nothing_without_the_spans():
    run = _run([], [_request()])
    run.probes = None
    assert all(reader(name)(run) is None for name in NEW)
    # a program whose spans carry no CPU time and no encoding spans
    bare = _trace(_span("execute", 0.1))
    bare.root.children[0].cpu = None
    run = _run([(101.0, bare)], [_request()])
    assert reader("encode_ms_mean")(run) is None
    assert reader("host_offcpu_share")(run) is None
    assert reader("exec_host_ms_mean")(run) == pytest.approx(100.0)
