"""Tracing daemon + interceptor unit tests."""
import gc
import json
import os
import time

import pytest

from repro.core.daemon import DaemonConfig, TracingDaemon
from repro.core.events import (EventKind, EventRingBuffer, TraceEvent,
                               load_jsonl)
from repro.core.interceptor import parse_api_spec
from repro.kernels import traced_op


def test_parse_api_spec():
    assert parse_api_spec("gc@collect, json@dumps") == [
        ("gc", "collect"), ("json", "dumps")]
    with pytest.raises(ValueError):
        parse_api_spec("nodelimiter")


def test_event_codec_roundtrip():
    ev = TraceEvent(EventKind.KERNEL_COMM, "allreduce", 3, 1.0, 1.5, 2.0,
                    step=7, meta={"bytes": 1024, "group": "dp"})
    ev2 = TraceEvent.from_json(ev.to_json())
    assert ev2.name == "allreduce" and ev2.rank == 3
    assert ev2.issue_latency == pytest.approx(0.5)
    assert ev2.meta["bytes"] == 1024


def test_ring_buffer_overflow():
    buf = EventRingBuffer(capacity=4)
    for i in range(7):
        buf.append(TraceEvent(EventKind.STEP, f"e{i}", 0, i, i, i + 1))
    assert buf.dropped == 3
    names = [e.name for e in buf.drain()]
    assert names == ["e3", "e4", "e5", "e6"]
    assert len(buf) == 0


def test_daemon_traces_env_api_gc_and_kernels(tmp_path):
    os.environ["FLARE_TRACED_PYTHON_API"] = "json@dumps"
    try:
        log = str(tmp_path / "t.jsonl")
        d = TracingDaemon(DaemonConfig(rank=1, log_path=log,
                                       drain_interval=0.01,
                                       hang_timeout=1e9))
        d.attach()
        got = []
        d.add_sink(lambda evs: got.extend(evs))
        d.step_begin(0)
        json.dumps([1, 2, 3])
        gc.collect()

        # the op library's entry points report to the attached daemon
        @traced_op("k1", "compute", lambda x: {"flops": 10.0})
        def op(x):
            return x * 2

        op(21)
        d.step_end(tokens=64)
        time.sleep(0.25)
        d.detach()
        kinds = {e.kind for e in got}
        assert EventKind.GC in kinds
        assert EventKind.STEP in kinds
        assert any(e.name == "json@dumps" for e in got)
        k = [e for e in got if e.name == "k1"]
        assert k and k[0].meta["flops"] == 10.0
        # kernel nests under the step span (stack reconstruction)
        assert k[0].meta.get("parent") == "step_0"
        # logged bytes and reload
        assert d.bytes_logged > 0
        reloaded = load_jsonl(log)
        assert len(reloaded) == len(got)
        # observer-effect guard: daemon's own json.dumps not traced
        dumps_count = sum(1 for e in got if e.name == "json@dumps")
        assert dumps_count == 1
    finally:
        del os.environ["FLARE_TRACED_PYTHON_API"]


def test_span_is_a_flare_span_and_a_profiler_annotation(tmp_path):
    """``span`` records the block as a span of the step and opens a
    profiler annotation of the same name; the block's own time is not
    Flare's self time."""
    import jax
    d = TracingDaemon(DaemonConfig(rank=0, drain_interval=0.01,
                                   hang_timeout=1e9))
    got = []
    d.add_sink(got.extend)
    d.attach()
    d.step_begin(3)
    jax.profiler.start_trace(str(tmp_path))
    with d.span(EventKind.PY_API, "phase.x", tokens=8) as s:
        time.sleep(0.01)
    jax.profiler.stop_trace()
    d.step_end()
    d.detach()
    ev, = [e for e in got if e.name == "phase.x"]
    assert (ev.kind, ev.step, ev.meta["tokens"]) == (EventKind.PY_API, 3, 8)
    assert (ev.start_ts, ev.end_ts) == (s.t0, s.t1)
    assert s.t1 - s.t0 >= 0.01
    step, = [e for e in got if e.kind == EventKind.STEP]
    assert 0 < step.meta["flare_self_ns"] == d.self_ns < 1e7
    trace, = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(trace))
    assert "phase.x" in {e.name for p in data.planes
                         if p.name.startswith("/host:")
                         for line in p.lines for e in line.events}


def test_daemon_counts_failing_sinks():
    """A sink that raises is counted in the telemetry registry; the other
    sinks still receive every event and the daemon thread lives on."""
    d = TracingDaemon(DaemonConfig(rank=0, drain_interval=0.01,
                                   hang_timeout=1e9))
    got = []

    def broken(_):
        raise OSError("sink down")

    d.add_sink(broken)
    d.add_batch_sink(broken)
    d.add_sink(got.extend)
    d.attach()
    for step in range(3):
        d.step_begin(step)
        d.step_end(tokens=8)
        time.sleep(0.05)
    d.detach()
    assert [e.name for e in got if e.kind == EventKind.STEP] == [
        "step_0", "step_1", "step_2"]
    # every drain fails both broken sinks, so the count is even and >= 2
    assert d.sink_errors >= 2 and d.sink_errors % 2 == 0
    assert d.telemetry.value("daemon.sink_errors") == d.sink_errors


def test_daemon_hang_heartbeat():
    d = TracingDaemon(DaemonConfig(rank=0, hang_timeout=0.05,
                                   drain_interval=0.01))
    d.attach()
    reports = []
    d.on_hang(reports.append)
    d.step_begin(0)
    d.set_stack(["train_step", "allreduce"])
    time.sleep(0.3)
    d.detach()
    assert reports and reports[0]["stack"] == ["train_step", "allreduce"]
