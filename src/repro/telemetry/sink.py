"""`MetricsSink` + the module-global hook surface (DESIGN.md §3.14).

Zero-cost-when-off: the hot paths (drivers, streams, pager, checkpoint io)
call the MODULE-LEVEL `span`/`counter`/`round_metrics` helpers, which read
one module global and return immediately (a shared no-op context manager
for spans) when no sink is installed. Nothing telemetry-shaped is ever
threaded through jit — the census job pins that the traced step's jaxpr is
byte-identical with a sink attached (`census-telemetry-identity`).

No extra device syncs when ON: `round_metrics`/`counter` values may be jax
arrays (the step's metrics pytree). The sink never materializes them on the
calling thread — records go onto a queue as-is and the BACKGROUND WRITER
thread converts them (`_jsonable` -> `np.asarray`), so the one
device->host fetch the loop already pays happens off the dispatch path.
Spans read `time.perf_counter()` twice and never call `block_until_ready`,
so a span measures host phase time (dispatch, not device completion) by
construction. Each span also enters a `jax.profiler.TraceAnnotation` of its
bare name, so under `jax.profiler.trace` it lands in the profiler's host
plane on the device trace's clock; an idle gap on the device can then be
put down to the host phase that spans it. `jax.profiler` is imported when
the first span is entered, never by `import repro.telemetry`.

Thread model: builds/spans fire from both the round loop and the prefetch
worker, so emission is queue-based (`queue.SimpleQueue`, lock-free put)
and span nesting depth is tracked per-thread.
"""
from __future__ import annotations

import json
import queue
import threading
import time
from contextlib import contextmanager

import numpy as np

from repro.telemetry.events import SCHEMA_VERSION

_CLOSE = object()


def _jsonable(v):
    """Materialize one record value for JSON. Runs on the WRITER thread
    (or at `events()` read time for in-memory sinks) — this is where jax
    scalars finally sync to host, off the round loop's critical path."""
    if v is None or isinstance(v, (str, bool, int, float)):
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    arr = np.asarray(v)  # jax/np scalars land here: the one host fetch
    return arr.item() if arr.ndim == 0 else arr.tolist()


class _Span:
    """One host phase interval; records (ts, dur, tid, depth) on exit and
    mirrors it as a profiler host annotation."""

    __slots__ = ("_sink", "_name", "_args", "_t0", "_depth", "_trace")

    def __init__(self, sink: "MetricsSink", name: str, args: dict):
        self._sink = sink
        self._name = name
        self._args = args

    def __enter__(self):
        tls = self._sink._tls
        self._depth = getattr(tls, "depth", 0)
        tls.depth = self._depth + 1
        # imported here, not with the module: repro.telemetry stays
        # numpy-only. The bare name: args would be encoded into the
        # profiler event's name (`name#k=v#`), so they stay in the record
        from jax.profiler import TraceAnnotation

        self._trace = TraceAnnotation(self._name)
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._trace.__exit__(None, None, None)
        sink = self._sink
        sink._tls.depth = self._depth
        rec = {"v": SCHEMA_VERSION, "kind": "span",
               "ts": self._t0 - sink._epoch, "dur": t1 - self._t0,
               "name": self._name, "tid": threading.get_ident(),
               "depth": self._depth}
        if self._args:
            rec["args"] = self._args
        sink._emit(rec)
        return False


class MetricsSink:
    """Append-only JSONL event stream with a buffered background writer.

    path=None keeps events in memory (`events()`) — used by tests and the
    census identity check. With a path, a daemon writer thread drains the
    emission queue, materializes values, and flushes every `flush_every`
    records (and at close), so an interrupted run loses at most the torn
    tail `read_events` already tolerates.
    """

    def __init__(self, path: str | None = None, *, flush_every: int = 64):
        self.path = path
        self._epoch = time.perf_counter()
        self._tls = threading.local()
        self._closed = False
        self._mem: list[dict] = []
        self._q: queue.SimpleQueue | None = None
        self._thread: threading.Thread | None = None
        self._file = None
        self._flush_every = max(1, int(flush_every))
        if path is not None:
            self._file = open(path, "w")
            self._q = queue.SimpleQueue()
            self._thread = threading.Thread(
                target=self._drain, name="telemetry-writer", daemon=True)
            self._thread.start()

    # -- emission ----------------------------------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self._epoch

    def _emit(self, rec: dict) -> None:
        if self._closed:
            return
        if self._q is not None:
            self._q.put(rec)
        else:
            self._mem.append(rec)  # GIL-atomic append: thread-safe

    def _drain(self) -> None:
        n = 0
        while True:
            rec = self._q.get()
            if rec is _CLOSE:
                break
            self._file.write(json.dumps(_jsonable(rec)) + "\n")
            n += 1
            if n % self._flush_every == 0:
                self._file.flush()
        self._file.flush()

    # -- record constructors ----------------------------------------------

    def run_meta(self, meta: dict) -> None:
        self._emit({"v": SCHEMA_VERSION, "kind": "run_meta",
                    "ts": self._now(), "meta": meta})

    def round_metrics(self, rnd: int, metrics: dict) -> None:
        """Values may be live jax arrays — materialized on the writer
        thread, never here (the no-extra-syncs argument)."""
        self._emit({"v": SCHEMA_VERSION, "kind": "round_metrics",
                    "ts": self._now(), "round": int(rnd),
                    "metrics": dict(metrics)})

    def counter(self, name: str, value, *, round: int | None = None,
                **tags) -> None:
        rec = {"v": SCHEMA_VERSION, "kind": "counter", "ts": self._now(),
               "name": name, "value": value}
        if round is not None:
            rec["round"] = int(round)
        if tags:
            rec["tags"] = tags
        self._emit(rec)

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    # -- reads / lifecycle -------------------------------------------------

    def events(self) -> list[dict]:
        """Materialized in-memory events (path=None sinks only)."""
        if self.path is not None:
            raise RuntimeError(
                "this sink writes to a file — close() it and use "
                "telemetry.read_events(path)")
        return [_jsonable(r) for r in list(self._mem)]

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._q is not None:
            self._q.put(_CLOSE)
            self._thread.join()
            self._file.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# the module-global hook surface (what instrumented code calls)
# ---------------------------------------------------------------------------

_ACTIVE: MetricsSink | None = None


class _NoopSpan:
    """Shared do-nothing context manager: the telemetry-off span cost is
    one global load, one None check, and returning this singleton."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


def install(sink: MetricsSink) -> MetricsSink:
    """Make `sink` the process-wide active sink (returns it)."""
    global _ACTIVE
    _ACTIVE = sink
    return sink


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> MetricsSink | None:
    return _ACTIVE


def enabled() -> bool:
    return _ACTIVE is not None


def span(name: str, **args):
    s = _ACTIVE
    return _NOOP if s is None else s.span(name, **args)


def counter(name: str, value, *, round: int | None = None, **tags) -> None:
    s = _ACTIVE
    if s is not None:
        s.counter(name, value, round=round, **tags)


def round_metrics(rnd: int, metrics: dict) -> None:
    s = _ACTIVE
    if s is not None:
        s.round_metrics(rnd, metrics)


def run_meta(meta: dict) -> None:
    s = _ACTIVE
    if s is not None:
        s.run_meta(meta)


@contextmanager
def session(sink: MetricsSink):
    """install -> yield -> uninstall + close, exception-safe."""
    install(sink)
    try:
        yield sink
    finally:
        uninstall()
        sink.close()


class ConsoleReporter:
    """The train.py round/step reporter (replaces its hand-rolled prints).

    Rates are monotonic (`time.perf_counter`) and measure the stepping
    window only: `start()` is called after checkpoint restore / stream
    construction, and checkpoint writes happen outside the reported window
    — so checkpoint I/O time is never folded into s/round.
    """

    def __init__(self, *, unit: str = "step", log_every: int = 10,
                 total: int | None = None, start: int = 0):
        self.unit = unit
        self.log_every = max(1, int(log_every))
        self.total = total
        self._start = int(start)
        self._t0: float | None = None

    def start(self) -> "ConsoleReporter":
        self._t0 = time.perf_counter()
        return self

    def report(self, t: int, metrics: dict, *, cohort: int | None = None
               ) -> None:
        if self._t0 is None:
            self.start()
        last = self.total is not None and t == self.total - 1
        if t % self.log_every != 0 and not last:
            return
        if metrics.get("skipped"):
            print(f"{self.unit} {t:5d} | skipped (buffer never filled)",
                  flush=True)
            return
        rate = (time.perf_counter() - self._t0) / (t - self._start + 1)
        part = (f" | done {int(metrics['completed'])}/{cohort}"
                if cohort is not None and "completed" in metrics else "")
        print(f"{self.unit} {t:5d} | loss {float(metrics['loss']):8.4f} | "
              f"gnorm {float(metrics['grad_norm']):9.3f} | "
              f"{rate:6.2f}s/{self.unit}" + part, flush=True)
