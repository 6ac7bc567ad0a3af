"""Per-process tracing daemon (paper §4): timing manager + background thread.

Responsibilities (mirroring Fig 4):
  * collect spans from the Python interceptor, the training loop's phases
    (``span``, each also a ``jax.profiler`` annotation on the profiler's
    clock), GC, the op library's kernel entry points, and step boundaries;
  * time asynchronous device work without blocking the training thread —
    completion probing happens on the daemon thread against shadow futures
    (the CUDA-event analogue; see DESIGN.md §2);
  * reconstruct Python<->kernel call stacks from span intervals (stack.py)
    before streaming;
  * heartbeat: if no event completes within ``hang_timeout`` while a step
    is in flight, report a suspected hang to the engine;
  * stream, in the background, to any sink: the in-process diagnostic
    engine and/or a JSONL file.

Kernel registration is the explicit "C++ interface" of the paper: the op
library's entry points (``repro.kernels.traced_op``) report to the attached
daemon; backends are never patched.

Flare's own cost on the training thread is counted where it is spent:
``span``/``record_span``, ``step_begin``, ``step_end``, ``set_stack`` and
the interceptor's callbacks add their bookkeeping time (never the user
code a span times) to the step's count, which ``step_end`` puts on the
STEP event as ``flare_self_ns`` and adds to the ``daemon.self_ns``
counter.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from repro.core.events import EventKind, EventRingBuffer, TraceEvent
from repro.core.interceptor import PyApiInterceptor
from repro.core.stack import reconstruct_stacks
from repro.core.telemetry import TelemetryRegistry

_GLOBAL_DAEMON: Optional["TracingDaemon"] = None
_ANNOTATION = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use so that the
    daemon's other users never import JAX."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


@dataclass
class DaemonConfig:
    rank: int = 0
    backend: str = "dense-train"   # historical-profile key (paper §8.2)
    hang_timeout: float = 30.0
    drain_interval: float = 0.05
    log_path: Optional[str] = None
    # spill codec: None = infer from log_path extension ("jsonl" default;
    # ".fcs" spills binary columnar segments, ".fcs2" compressed archival
    # segments — see repro.store).  "fcs2" may also be named explicitly
    # to write v2 segments into a ".fcs" path (readers dispatch on the
    # segment version byte, so mixed files replay fine).
    log_codec: Optional[str] = None
    # archival-spill compression: backend name ("zstd"/"zlib"; None =
    # best available) and level for FCS v2 segments.  Setting either
    # implies log_codec="fcs2".
    log_compression: Optional[str] = None
    log_compression_level: Optional[int] = None
    # rotate the spill to <stem>.segNNN<ext> once the current file passes
    # this size; None = single file forever (historical behavior)
    log_rotate_bytes: Optional[int] = None
    buffer_capacity: int = 200_000
    reconstruct: bool = True
    enabled: bool = True
    # detector set for the engine diagnosing this daemon's job when it is
    # attached to a fleet without an explicit EngineConfig (registry names
    # / DetectorSpecs — see repro.core.detectors); None = default set
    detectors: Optional[list] = None
    num_ranks: int = 1             # job-wide rank count for that engine
    # self-telemetry registry (repro.core.telemetry); None = a private
    # one per daemon.  Pass a shared registry (or attach to a fleet,
    # whose snapshot merges daemon registries) for one pipeline view.
    telemetry: Optional[TelemetryRegistry] = None
    # live fleet service endpoint ("host:port"): each flushed batch is
    # FCS-framed (repro.serve wire protocol) and shipped from the daemon
    # thread with reconnect/backoff; a dead or slow service costs
    # COUNTED drops (daemon.live_dropped) — it can never block the
    # heartbeat or kill the daemon, and the spill/tail plane recovers
    # whatever live frames were lost
    live_endpoint: Optional[str] = None
    live_job_id: Optional[str] = None      # default: "job-rank<rank>"
    live_topology: Optional[dict] = None   # rack/switch attrs, HELLO'd


class TracingDaemon:
    def __init__(self, config: DaemonConfig | None = None):
        self.cfg = config or DaemonConfig()
        self.buffer = EventRingBuffer(self.cfg.buffer_capacity)
        self.interceptor = PyApiInterceptor(self._on_api_span, self._on_gc)
        self._sinks: list[Callable[[list[TraceEvent]], None]] = []
        self._batch_sinks: list = []
        self._hang_cb: Optional[Callable[[dict], None]] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._step = -1
        self._step_t0 = 0.0
        self._in_step = False
        self._last_completion = time.perf_counter()
        self._pending: "queue.Queue" = queue.Queue()
        self._last_stack: list[str] = []
        # self-telemetry: handles resolved once, incremented lock-free on
        # the hot path (these replace the old plain-int attributes; the
        # read-only properties below keep that surface)
        self.telemetry = self.cfg.telemetry or TelemetryRegistry()
        self._c_bytes = self.telemetry.counter("daemon.bytes_logged")
        self._c_events = self.telemetry.counter("daemon.events_emitted")
        self._c_spill_errors = self.telemetry.counter("daemon.spill_errors")
        self._c_sink_errors = self.telemetry.counter("daemon.sink_errors")
        self._c_self_ns = self.telemetry.counter("daemon.self_ns")
        self._self_ns = 0   # this step's bookkeeping so far, in ns
        self._g_heartbeat = self.telemetry.gauge("daemon.heartbeat_age_s")
        self._g_queue = self.telemetry.gauge("daemon.queue_depth")
        self._g_rate = self.telemetry.gauge("daemon.events_per_s")
        self._rate_t0 = time.perf_counter()
        self._rate_n0 = 0
        self._attached = False
        self._spill = None
        if self.cfg.log_path:
            from repro.store import FcsV2Codec, SegmentedTraceWriter
            codec = self.cfg.log_codec
            if (self.cfg.log_compression is not None
                    or self.cfg.log_compression_level is not None):
                # an explicit compression knob means the archival (v2)
                # spill, with a per-daemon backend/level instance
                codec = FcsV2Codec(
                    compression=self.cfg.log_compression,
                    level=self.cfg.log_compression_level)
            self._spill = SegmentedTraceWriter(
                self.cfg.log_path, codec=codec,
                rotate_bytes=self.cfg.log_rotate_bytes)
        self._live = None
        if self.cfg.live_endpoint:
            from repro.serve.client import LiveBatchSink
            self._live = LiveBatchSink(
                self.cfg.live_endpoint,
                self.cfg.live_job_id or f"job-rank{self.cfg.rank}",
                topology=self.cfg.live_topology,
                telemetry=self.telemetry)
            self.add_batch_sink(self._live)

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #
    def attach(self):
        """Attach to the current training process (plug-and-play)."""
        if self._attached or not self.cfg.enabled:
            return self
        self.interceptor.register_from_env()
        self.interceptor.install()
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="flare-daemon")
        self._thread.start()
        self._attached = True
        global _GLOBAL_DAEMON
        _GLOBAL_DAEMON = self
        return self

    def detach(self):
        if not self._attached:
            return
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        self.interceptor.uninstall()
        self._flush()
        if self._live is not None:
            self._live.close()        # best-effort BYE; reconnects if
            #                           the daemon re-attaches later
        self._attached = False
        global _GLOBAL_DAEMON
        if _GLOBAL_DAEMON is self:
            _GLOBAL_DAEMON = None

    def stop(self):
        """Idempotent shutdown: safe on a never-attached or already-stopped
        daemon and safe to call repeatedly — the fleet close path stops
        every job's daemons without tracking which already exited."""
        self.detach()

    def attach_fleet(self, mux, job_id: Optional[str] = None,
                     engine_cfg=None):
        """Fleet seam: stream this daemon's drains into a
        ``repro.fleet.FleetMultiplexer`` as job ``job_id`` (columnar batch
        sink, no per-event dicts) and hand the daemon to the multiplexer so
        ``mux.close()`` can ``stop()`` it with the rest of the fleet.

        ``engine_cfg`` configures the job's diagnostic engine (detector
        set, rank count).  Without one, the daemon builds it from its own
        config — ``DaemonConfig.detectors``/``num_ranks``/``backend`` —
        so a process can pick its diagnosis plugins at daemon-attach time
        without ever importing the engine."""
        jid = job_id if job_id is not None else f"job-rank{self.cfg.rank}"
        if engine_cfg is None and (self.cfg.detectors is not None
                                   or self.cfg.num_ranks > 1
                                   or self.cfg.backend != DaemonConfig.backend):
            # any non-default engine-relevant daemon setting wins over the
            # multiplexer's fallback EngineConfig; an all-default daemon
            # keeps the historical behavior (fleet-configured backend)
            from repro.core.engine import EngineConfig
            engine_cfg = EngineConfig(
                backend=self.cfg.backend, num_ranks=self.cfg.num_ranks,
                detectors=self.cfg.detectors)
        mux.register_daemon(jid, self, engine_cfg)
        self.add_batch_sink(lambda batch, _jid=jid: mux.ingest(_jid, batch))
        return self

    def add_sink(self, sink: Callable[[list[TraceEvent]], None]):
        self._sinks.append(sink)

    def add_batch_sink(self, sink):
        """Columnar sink: receives each drain as one ``EventBatch`` (e.g.
        ``engine.ingest_batch``), skipping per-event dict handling in the
        consumer."""
        self._batch_sinks.append(sink)

    def on_hang(self, cb: Callable[[dict], None]):
        self._hang_cb = cb

    # ------------------------------------------------------------------ #
    # event entry points
    # ------------------------------------------------------------------ #
    # telemetry-backed views of the historical plain-int attributes
    @property
    def bytes_logged(self) -> int:
        return self._c_bytes.value

    @property
    def events_emitted(self) -> int:
        return self._c_events.value

    @property
    def spill_errors(self) -> int:
        return self._c_spill_errors.value

    @property
    def sink_errors(self) -> int:
        return self._c_sink_errors.value

    def _emit(self, ev: TraceEvent):
        self.buffer.append(ev)
        self._c_events.inc()
        self._last_completion = time.perf_counter()

    @property
    def self_ns(self) -> int:
        return self._c_self_ns.value

    def _on_api_span(self, name: str, t0: float, t1: float):
        t = time.perf_counter_ns()
        self._emit(TraceEvent(EventKind.PY_API, name, self.cfg.rank,
                              t0, t0, t1, step=self._step))
        self._self_ns += time.perf_counter_ns() - t

    def _on_gc(self, name: str, t0: float, t1: float):
        t = time.perf_counter_ns()
        self._emit(TraceEvent(EventKind.GC, name, self.cfg.rank,
                              t0, t0, t1, step=self._step))
        self._self_ns += time.perf_counter_ns() - t

    def record_span(self, kind: EventKind, name: str, t0: float, t1: float,
                    **meta):
        t = time.perf_counter_ns()
        self._emit(TraceEvent(kind, name, self.cfg.rank, t0, t0, t1,
                              step=self._step, meta=meta))
        self._self_ns += time.perf_counter_ns() - t

    def span(self, kind: EventKind, name: str, **meta) -> "Span":
        """``with daemon.span(kind, name):`` times the block as a Flare
        span of this step, as ``record_span`` would, inside a
        ``jax.profiler.TraceAnnotation`` of the same name, so the block
        also lands on the profiler's host plane."""
        return Span(self, kind, name, meta, _trace_annotation())

    def step_begin(self, step: int):
        t = time.perf_counter_ns()
        self._step = step
        self._step_t0 = t * 1e-9
        self._in_step = True
        self._self_ns += time.perf_counter_ns() - t

    def step_end(self, **meta):
        """Emits the STEP event with the step's Flare self time; the time
        this call takes after that opens the next step's count."""
        t = time.perf_counter_ns()
        n = meta["flare_self_ns"] = self._self_ns
        self._c_self_ns.inc(n)
        self._emit(TraceEvent(EventKind.STEP, f"step_{self._step}",
                              self.cfg.rank, self._step_t0, self._step_t0,
                              t * 1e-9, step=self._step, meta=meta))
        self._in_step = False
        self._self_ns = time.perf_counter_ns() - t

    def set_stack(self, stack: list[str]):
        """Training thread publishes its logical call stack (hang analysis)."""
        t = time.perf_counter_ns()
        self._last_stack = list(stack)
        self._self_ns += time.perf_counter_ns() - t

    # ------------------------------------------------------------------ #
    # background thread: timing manager + heartbeat + streaming
    # ------------------------------------------------------------------ #
    def _run(self):
        while not self._stop.is_set():
            self._probe_pending()
            self._flush()
            self._heartbeat()
            time.sleep(self.cfg.drain_interval)
        self._probe_pending()
        self._flush()

    def _probe_pending(self):
        try:
            while True:
                name, kind, issue, step, out, meta = self._pending.get_nowait()
                start = time.perf_counter()
                try:
                    import jax
                    jax.block_until_ready(out)
                except Exception:
                    pass
                end = time.perf_counter()
                self._emit(TraceEvent(kind, name, self.cfg.rank, issue,
                                      start, end, step=step, meta=meta))
        except queue.Empty:
            pass

    def _flush(self):
        events = self.buffer.drain()
        if not events:
            return
        if self.cfg.reconstruct:
            reconstruct_stacks(events)
        # a failing sink must not kill the daemon thread (that would end
        # the hang heartbeat too); each failure is counted instead
        for sink in self._sinks:
            try:
                sink(events)
            except Exception:
                self._c_sink_errors.inc()
        if self._batch_sinks or self._spill is not None:
            from repro.core.columnar import EventBatch
            batch = EventBatch.from_events(events)
            for sink in self._batch_sinks:
                try:
                    sink(batch)
                except Exception:
                    self._c_sink_errors.inc()
            if self._spill is not None:
                # one codec segment (or JSONL line run) per drain; guarded
                # like the sinks — a spill error (disk full, unserializable
                # user meta) must not kill the daemon thread, which would
                # silently end hang-heartbeat detection too.  Counted and
                # warned once so a permanently failing spill is observable.
                try:
                    self._c_bytes.inc(self._spill.write(batch))
                except Exception as e:
                    if self._c_spill_errors.inc() == 1:
                        import warnings
                        warnings.warn(
                            f"trace spill to {self.cfg.log_path} failing "
                            f"({type(e).__name__}: {e}); events continue to "
                            "stream to sinks but are NOT being persisted",
                            stacklevel=2)

    @property
    def log_paths(self) -> list[str]:
        """Every spill file written so far (>1 once rotation kicks in)."""
        return list(self._spill.paths) if self._spill is not None else []

    def _heartbeat(self):
        now = time.perf_counter()
        silent = now - self._last_completion
        self._g_heartbeat.set(silent)
        self._g_queue.set(self._pending.qsize())
        dt = now - self._rate_t0
        if dt >= 1.0:
            n = self._c_events.value
            self._g_rate.set((n - self._rate_n0) / dt)
            self._rate_t0, self._rate_n0 = now, n
        if self._in_step and silent > self.cfg.hang_timeout:
            report = {"rank": self.cfg.rank, "silent_s": silent,
                      "step": self._step, "stack": self._last_stack}
            self._emit(TraceEvent(EventKind.HANG_SUSPECT, "hang_suspect",
                                  self.cfg.rank, now, now, now,
                                  step=self._step, meta=report))
            if self._hang_cb:
                try:
                    self._hang_cb(report)
                except Exception:
                    pass
            self._last_completion = now  # rate-limit repeat reports


class Span:
    """A block timed by ``TracingDaemon.span``.  ``t0`` and ``t1`` are its
    start and end on ``time.perf_counter``'s clock; the daemon's self
    time takes the rest of ``__enter__`` and ``__exit__``."""

    __slots__ = ("_daemon", "_kind", "_name", "_meta", "_created",
                 "_annotation", "t0", "t1")

    def __init__(self, daemon: TracingDaemon, kind: EventKind, name: str,
                 meta: dict, annotation):
        self._created = time.perf_counter_ns()
        self._daemon, self._kind, self._name = daemon, kind, name
        self._meta = meta
        self._annotation = annotation(name)

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        t0 = time.perf_counter_ns()
        self.t0 = t0 * 1e-9
        self._daemon._self_ns += t0 - self._created
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self.t1 = t1 * 1e-9
        self._annotation.__exit__(*exc)
        d = self._daemon
        d._emit(TraceEvent(self._kind, self._name, d.cfg.rank, self.t0,
                           self.t0, self.t1, step=d._step, meta=self._meta))
        d._self_ns += time.perf_counter_ns() - t1
        return False


# --------------------------------------------------------------------------- #
def attach(config: DaemonConfig | None = None) -> TracingDaemon:
    """Module-level convenience: attach a daemon to this process."""
    d = TracingDaemon(config)
    return d.attach()


def get_daemon() -> Optional[TracingDaemon]:
    return _GLOBAL_DAEMON
