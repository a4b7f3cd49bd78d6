"""Host spans for the GP stack: one span, two sinks.

Every span opens a `jax.profiler.TraceAnnotation` under its name, so
while a profiler session runs (`jax.profiler.trace`, `--profile-dir`,
a benchmark's traced window) the span sits on the profiler's host
plane beside the device operations it dispatched — on the profiler's
clock, where a device idle gap can be put down to the host span open
at that moment. With no profiler running an annotation records nothing
and costs about a microsecond. `span(name)` is that annotation alone:
`NULL_TRACER.span` returns it, and code with no tracer handle
(`core/engine.py`) calls it directly.

A `Tracer` additionally collects trace events in memory and writes the
Chrome Trace Event JSON object format (`{"traceEvents": [...]}`) — open
the file at `chrome://tracing` or https://ui.perfetto.dev to see
ingest, block dispatches, chunk folds, checkpoint saves and service
admission/dispatch as nested spans on a per-thread timeline, and
per-job lifetimes as async tracks. The Chrome sink keeps its own
`perf_counter` clock. The device programs never see the tracer at all
(the counter stream is unconditional; see obs/counters.py), which is
what keeps traced and untraced trajectories bitwise identical.

Span discipline: `Tracer.span()` emits a "B" event and ALWAYS emits the
matching "E" on exit (try/finally), so every written trace nests
properly — tests/test_obs.py walks the B/E stack per thread and
rejects orphans. Async job lifetimes use "b"/"e" events keyed by id.
`args` go to the Chrome sink only; the annotation carries the bare
name, so both sinks name a span alike.

An optional `jax.profiler` window can be armed around one chosen
evolution block (`profile_dir=`, `profile_block=`): the session asks
`maybe_profile(block_index)` at each dispatch and exactly that block
runs under `jax.profiler.start_trace` — device-level XLA timing for
one block, without paying profiler overhead for the whole run.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext

from jax.profiler import TraceAnnotation


def span(name: str) -> TraceAnnotation:
    """A host span on the profiler's clock: recorded under `name` while a
    profiler session runs, nothing otherwise. Pass the bare name — the
    annotation's keyword form would rename the event."""
    return TraceAnnotation(name)


class NullTracer:
    """Tracer with no Chrome sink: `span` is the profiler annotation
    alone, every other method returns immediately. Instrumented code
    calls the tracer unconditionally and never branches on enablement."""

    enabled = False

    def span(self, name, cat="repro", args=None):
        return span(name)

    def instant(self, name, cat="repro", args=None):
        pass

    def begin_async(self, name, aid, cat="repro", args=None):
        pass

    def end_async(self, name, aid, cat="repro", args=None):
        pass

    def maybe_profile(self, block_index):
        return nullcontext()

    def save(self, path=None):
        return None


NULL_TRACER = NullTracer()


class Tracer:
    """Collects Chrome trace events; thread-safe appends; one process.

    `path` (optional) is where `save()` writes by default; pass
    `profile_dir`/`profile_block` to arm a jax.profiler window around
    the `profile_block`-th dispatched evolution block."""

    enabled = True

    def __init__(self, path: str | None = None, *,
                 profile_dir: str | None = None,
                 profile_block: int | None = None):
        self.path = path
        self.profile_dir = profile_dir
        self.profile_block = (profile_block if profile_block is not None
                              else (0 if profile_dir else None))
        self.events: list[dict] = []
        self._lock = threading.Lock()
        self._async_open: set[tuple] = set()
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._emit({"ph": "M", "name": "process_name", "pid": self._pid,
                    "tid": 0, "args": {"name": "repro-gp"}})

    # --- low level ------------------------------------------------------------

    def _ts(self) -> float:
        """Microseconds since tracer construction (Chrome trace unit)."""
        return (time.perf_counter() - self._t0) * 1e6

    def _emit(self, ev: dict):
        with self._lock:
            self.events.append(ev)

    def _base(self, ph, name, cat, args):
        ev = {"ph": ph, "name": name, "cat": cat, "ts": self._ts(),
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = dict(args)
        return ev

    # --- spans / instants -----------------------------------------------------

    @contextmanager
    def span(self, name, cat="repro", args=None):
        """Duration span: B on entry, E on exit — the E is emitted even
        when the body raises, so traces always nest. The profiler
        annotation of the same name opens inside the pair."""
        self._emit(self._base("B", name, cat, args))
        try:
            with span(name):
                yield self
        finally:
            self._emit(self._base("E", name, cat, None))

    def instant(self, name, cat="repro", args=None):
        ev = self._base("i", name, cat, args)
        ev["s"] = "t"  # thread-scoped instant
        self._emit(ev)

    def begin_async(self, name, aid, cat="repro", args=None):
        """Open an async lifetime lane. Idempotent per (name, id): a
        rollback/replay path re-opening a live lane is a no-op, so the
        written trace always pairs b/e events."""
        ev = self._base("b", name, cat, args)
        ev["id"] = str(aid)
        with self._lock:
            key = (name, ev["id"])
            if key in self._async_open:
                return
            self._async_open.add(key)
            self.events.append(ev)

    def end_async(self, name, aid, cat="repro", args=None):
        """Close an async lane; a close with no open lane (replayed
        publish after a restart rollback) is a no-op."""
        ev = self._base("e", name, cat, args)
        ev["id"] = str(aid)
        with self._lock:
            key = (name, ev["id"])
            if key not in self._async_open:
                return
            self._async_open.discard(key)
            self.events.append(ev)

    # --- jax.profiler window --------------------------------------------------

    @contextmanager
    def _profile_window(self):
        import jax

        jax.profiler.start_trace(self.profile_dir)
        try:
            yield self
        finally:
            jax.profiler.stop_trace()

    def maybe_profile(self, block_index: int):
        """Context manager: a real jax.profiler window when this is the
        armed block, a no-op otherwise."""
        if self.profile_dir is not None and block_index == self.profile_block:
            return self._profile_window()
        return nullcontext()

    # --- output ---------------------------------------------------------------

    def save(self, path: str | None = None) -> str:
        """Write `{"traceEvents": [...]}` (the Chrome trace JSON object
        form — Perfetto and chrome://tracing both open it). Returns the
        path written."""
        path = path or self.path
        if path is None:
            raise ValueError("Tracer has no path — pass save(path) or "
                             "construct with Tracer(path)")
        with self._lock:
            events = list(self.events)
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f)
        return path


def validate_trace(payload: dict) -> list[str]:
    """Schema check for a Chrome trace object: returns a list of
    problems (empty = valid). Checks the envelope, per-(pid, tid) B/E
    stack discipline (no orphan E, no unclosed B, E names match their
    B), and that async b/e events pair up per (name, id)."""
    problems = []
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents is not a list"]
    stacks: dict[tuple, list] = {}
    async_open: dict[tuple, int] = {}
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph in ("B", "E"):
            key = (ev.get("pid"), ev.get("tid"))
            stack = stacks.setdefault(key, [])
            if ph == "B":
                stack.append(ev.get("name"))
            else:
                if not stack:
                    problems.append(f"event {i}: orphan E {ev.get('name')!r}")
                elif stack[-1] != ev.get("name"):
                    problems.append(
                        f"event {i}: E {ev.get('name')!r} closes "
                        f"B {stack[-1]!r} (misnested)")
                    stack.pop()
                else:
                    stack.pop()
        elif ph == "b":
            k = (ev.get("name"), ev.get("id"))
            async_open[k] = async_open.get(k, 0) + 1
        elif ph == "e":
            k = (ev.get("name"), ev.get("id"))
            if async_open.get(k, 0) < 1:
                problems.append(f"event {i}: async e without b for {k}")
            else:
                async_open[k] -= 1
    for (pid, tid), stack in stacks.items():
        for name in stack:
            problems.append(f"unclosed B {name!r} on (pid={pid}, tid={tid})")
    for k, n in async_open.items():
        if n:
            problems.append(f"async b without e for {k}")
    return problems
