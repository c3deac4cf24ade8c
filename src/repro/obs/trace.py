"""Tracing: nestable spans over a fixed-size ring buffer of events.

The stack's observability tentpole needs a tracer that is *free when
off* and *cheap when on*:

* **off** — the module-level current tracer defaults to
  :data:`NULL_TRACER`, a shared constant whose ``enabled`` flag is
  ``False`` and whose ``span``/``event`` methods are no-ops returning a
  shared no-op context.  Every instrumented call site branches on
  ``tracer().enabled`` *before* doing any tag computation, so the
  disabled path is one global read + one attribute check — no event
  objects, no clock reads, no extra dispatches, identical program-cache
  keys, bit-identical outputs (``tests/test_obs.py`` pins this).
* **on** — events land in a preallocated ring buffer by monotonically
  increasing sequence number (an integer index modulo capacity; under
  the GIL the append is a single list-slot store, so concurrent
  emitters never block each other — "lock-free" in the
  no-locks-on-the-hot-path sense).  The buffer holds the most recent
  ``capacity`` events; ``seq`` stays globally monotone so drops are
  detectable.

Event schema (one dict per event — the *unified* schema; the fault
injector emits onto the same stream, see ``repro.launch.faults``):

  ``{"seq": int, "ts": float, "kind": "begin"|"end"|"point",
     "name": str, "span": int, "parent": int | None, "tags": dict}``

``span`` is the owning span's id for begin/end pairs (and the enclosing
span for points; 0 = top level); ``parent`` is the enclosing span's id.
``end`` events carry ``tags["dur"]`` (seconds), plus any tags the span
set while open (``span.set(...)``: values known only at its close).
The clock is injectable (``Tracer(clock=...)``) so span
ordering/duration tests run deterministically under a fake clock.

**Profiler mirror.**  Every span of an enabled tracer also opens a
``jax.profiler.TraceAnnotation`` of the same name (imported on first
use), so under ``jax.profiler.start_trace`` the program's spans land on
the host plane of the device trace, on the profiler's clock, beside the
device operations.  While an enabled tracer is current, ``set_tracer``
also installs a ``gc.callbacks`` hook that records every collection as
a ``gc.collect`` span; installing the null tracer removes it.

Span taxonomy (see README "Observability"):

  ``engine.denoise|select|full_scan|fused_step``  one per engine entry
      dispatch (``fused_step`` when the fused="auto" policy routes the
      step through the single-pass fused program)
  ``stage.screen|ivf_screen|rerank|aggregate|fused_step``  not host
      events: ``jax.named_scope``s around each stage inside the
      compiled step (``core.engine``), so every device operation of the
      step carries its stage in its ``tf_op`` metadata in a profiler trace
  ``dispatch.<kind>``  one per program-cache dispatch (TraceHook)
  ``plan.segment``     one per trajectory-plan bucket execution
  ``runtime.pump``     one serving-runtime scheduler step, containing
      ``runtime.admit`` (expiry, seam compaction, admission, wave pick;
      tags ``queue_depth`` then ``joins``/``waves``), ``runtime.noise``
      (a terminal-noise device round trip for a new wave or a join),
      ``wave.segment`` and ``runtime.post`` (delivery, repacking, epoch
      collection)
  ``wave.segment``     one per serving-runtime segment, containing
      ``wave.h2d`` (host-to-device copy of the wave, ``bytes``),
      ``wave.execute`` (dispatch through ``block_until_ready``),
      ``wave.d2h`` (device-to-host copy, ``bytes``) and ``wave.guard``
      (the per-row finite guard); plus ``wave.*`` / ``request.*``
      lifecycle points
  ``gc.collect``       one per Python garbage collection (``generation``,
      then ``collected``/``uncollectable``)
  ``fault.<kind>``     injected faults, inline (launch.faults)
"""
from __future__ import annotations

import gc
import json
import time


class _NullSpan:
    """Shared no-op context manager (the disabled-tracer span)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **tags):
        return None


_NULL_SPAN = _NullSpan()
_ANNOTATION = None          # jax.profiler.TraceAnnotation, on first use


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` named ``name``."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation   # deferred: obs stays light
        _ANNOTATION = TraceAnnotation
    ann = _ANNOTATION(name)
    ann.__enter__()
    return ann


class _Span:
    """Live span handle: closes with an ``end`` event carrying ``dur``
    and whatever :meth:`set` added."""

    __slots__ = ("tracer", "name", "sid", "parent", "t0", "ann", "end_tags")

    def __init__(self, tracer, name, sid, parent, t0, ann):
        self.tracer = tracer
        self.name = name
        self.sid = sid
        self.parent = parent
        self.t0 = t0
        self.ann = ann
        self.end_tags = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.tracer._end_span(self)
        return False

    def set(self, **tags):
        """Tags for the ``end`` event: values known only at the close."""
        if self.end_tags is None:
            self.end_tags = tags
        else:
            self.end_tags.update(tags)


class Tracer:
    """Nestable spans + point events over a bounded ring buffer."""

    enabled = True

    def __init__(self, capacity: int = 4096, clock=time.perf_counter):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.clock = clock
        self._buf: list = [None] * self.capacity
        self._seq = 0                     # next sequence number (monotone)
        self._next_span = 1               # span id 0 = top level
        self._stack: list[int] = []       # open span ids (nesting)

    # -- emission -------------------------------------------------------------
    def _emit(self, kind: str, name: str, span: int, parent, tags: dict):
        seq = self._seq
        self._seq = seq + 1
        self._buf[seq % self.capacity] = {
            "seq": seq, "ts": self.clock(), "kind": kind, "name": name,
            "span": span, "parent": parent, "tags": tags}

    def span(self, name: str, **tags):
        """Open a nested span; use as ``with tr.span("engine.denoise",
        t=400):``.  The matching ``end`` event records ``dur``."""
        parent = self._stack[-1] if self._stack else 0
        sid = self._next_span
        self._next_span += 1
        ann = _annotation(name)
        t0 = self.clock()
        self._emit("begin", name, sid, parent, tags)
        self._stack.append(sid)
        return _Span(self, name, sid, parent, t0, ann)

    def _end_span(self, s: _Span):
        if self._stack and self._stack[-1] == s.sid:
            self._stack.pop()
        elif s.sid in self._stack:        # tolerate mis-nested exits
            self._stack.remove(s.sid)
        tags = {"dur": self.clock() - s.t0}
        if s.end_tags:
            tags.update(s.end_tags)
        self._emit("end", s.name, s.sid, s.parent, tags)
        s.ann.__exit__(None, None, None)

    def event(self, name: str, **tags):
        """Point event inside the current span (0 = top level)."""
        span = self._stack[-1] if self._stack else 0
        self._emit("point", name, span,
                   self._stack[-2] if len(self._stack) > 1 else None, tags)

    # -- reading --------------------------------------------------------------
    def events(self) -> list[dict]:
        """Buffered events in sequence order (oldest surviving first)."""
        n = min(self._seq, self.capacity)
        start = self._seq - n
        return [self._buf[(start + i) % self.capacity] for i in range(n)]

    @property
    def dropped(self) -> int:
        """Events evicted by ring wrap (total emitted - buffered)."""
        return max(0, self._seq - self.capacity)

    def clear(self) -> None:
        self._buf = [None] * self.capacity
        self._seq = 0
        self._next_span = 1
        self._stack = []

    def dump(self, path: str) -> int:
        """Write buffered events as JSON lines; returns the count."""
        evs = self.events()
        with open(path, "w") as f:
            for e in evs:
                f.write(json.dumps(e, default=str) + "\n")
        return len(evs)


class NullTracer(Tracer):
    """The disabled tracer: every operation is a no-op constant."""

    enabled = False

    def __init__(self):
        super().__init__(capacity=1)

    def span(self, name: str, **tags):
        return _NULL_SPAN

    def event(self, name: str, **tags):
        return None

    def _emit(self, *a, **kw):
        return None


NULL_TRACER = NullTracer()

_TRACER: Tracer = NULL_TRACER


def set_tracer(tr: Tracer | None) -> Tracer:
    """Install ``tr`` (or NULL_TRACER for ``None``) as the process-wide
    current tracer; returns the previous one so callers can restore.
    The ``gc.collect`` hook is installed exactly while the current
    tracer is enabled."""
    global _TRACER
    prev = _TRACER
    _TRACER = NULL_TRACER if tr is None else tr
    if _TRACER.enabled:
        if _gc_hook not in gc.callbacks:
            gc.callbacks.append(_gc_hook)
    elif _gc_hook in gc.callbacks:
        gc.callbacks.remove(_gc_hook)
    return prev


def tracer() -> Tracer:
    """The current tracer (NULL_TRACER when tracing is off)."""
    return _TRACER


_GC_SPAN = None


def _gc_hook(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: each collection as a ``gc.collect`` span."""
    global _GC_SPAN
    if phase == "start":
        _GC_SPAN = _TRACER.span("gc.collect", generation=info["generation"])
    elif _GC_SPAN is not None:
        _GC_SPAN.set(collected=info["collected"],
                     uncollectable=info["uncollectable"])
        _GC_SPAN.__exit__(None, None, None)
        _GC_SPAN = None


class TraceHook:
    """Dispatch-seam hook: spans every compiled-program dispatch.

    Installed at ``ops.set_dispatch_hook`` (the same seam the fault
    injector uses).  ``inner`` chains to a previously installed hook —
    typically the :class:`repro.launch.faults.FaultInjector` — so
    tracing and fault injection compose; the injector's wrapped
    callable runs *inside* the trace span, so injected latency/errors
    are attributed to the dispatch that suffered them.

    Each dispatch emits a ``dispatch.<kind>`` span tagged with the full
    cache key and ``compile`` (True exactly when this lookup built the
    program — detected pre-lookup via ``key in engine._programs``).
    ``registry`` (optional, a ``repro.obs.metrics.MetricsRegistry``)
    additionally counts dispatches and compiles per program kind.
    """

    def __init__(self, tr: Tracer, inner=None, registry=None):
        self.tracer = tr
        self.inner = inner
        self.registry = registry
        self._last_compile = False

    def on_program(self, engine, key) -> None:
        if self.inner is not None:
            self.inner.on_program(engine, key)   # may evict (recompile)
        # ``program()`` calls on_program then wrap back-to-back for the
        # same key, so one pending flag is enough (no interleaving)
        self._last_compile = key not in engine._programs

    def wrap(self, key, fn):
        if self.inner is not None:
            fn = self.inner.wrap(key, fn)
        tr = self.tracer
        if not tr.enabled and self.registry is None:
            return fn
        kind = key[0] if isinstance(key, tuple) and key else str(key)
        compiled = bool(self._last_compile)
        if self.registry is not None:
            self.registry.counter(f"golddiff_dispatch_total_{kind}").inc()
            if compiled:
                self.registry.counter("golddiff_compiles_total").inc()
        if not tr.enabled:
            return fn

        def traced(*args, **kw):
            with tr.span(f"dispatch.{kind}", key=repr(key),
                         compile=compiled):
                return fn(*args, **kw)

        return traced


def install_dispatch_tracing(tr: Tracer, registry=None) -> TraceHook:
    """Wrap the current dispatch hook (e.g. an installed fault
    injector) in a :class:`TraceHook` and install it.  Returns the hook
    so callers can pass it to :func:`uninstall_dispatch_tracing`."""
    from repro.kernels import ops   # deferred: keep obs import-light
    hook = TraceHook(tr, inner=ops.dispatch_hook(), registry=registry)
    ops.set_dispatch_hook(hook)
    return hook


def uninstall_dispatch_tracing(hook: TraceHook | None = None) -> None:
    """Restore the hook that was active before tracing was installed."""
    from repro.kernels import ops
    cur = ops.dispatch_hook()
    if isinstance(cur, TraceHook):
        ops.set_dispatch_hook(cur.inner)
    elif hook is not None and cur is hook:   # pragma: no cover - defensive
        ops.set_dispatch_hook(hook.inner)
