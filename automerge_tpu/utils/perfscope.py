"""Performance plane: compile telemetry, phase attribution, memory gauges.

PRs 1-3 made the repo observable for *liveness* (spans, watchdog, flight
recorder, convergence audit); this module is the matching *performance*
plane the ROADMAP north star ("as fast as the hardware allows") needs to
be checkable run over run:

- **compile telemetry** — `metrics.dispatch_jit` routes every jitted
  kernel call through `dispatch_begin()`/`dispatch_end()` here. Compile
  events are observed exactly via `jax.monitoring` duration listeners
  (the cpp jit cache fires `/jax/core/compile/*` events only on a real
  cache miss), attributed to the dispatching kernel through a
  thread-local marker stack — replacing the old `_cache_size()` delta,
  which was thread-racy and misattributed concurrent dispatches. On the
  first sighting of a (kernel, abstract-signature) pair the kernel is
  also analyzed ahead of the call: `fn.lower(...)` for XLA
  `cost_analysis()` flops/bytes and (mode `full`) an AOT
  `lowered.compile()` for `memory_analysis()` HBM sections. Results
  land as registered gauges (`engine_kernel_flops{kernel=...}`,
  `engine_kernel_hbm_bytes{kernel=...,section=...}`) and in the `perf`
  section of `metrics.snapshot()`.
- **phase attribution** — `phase(name)` is one host accumulator AND one
  `jax.profiler.TraceAnnotation` from one call site: the wall time lands
  under one of the registered PHASES (`phase.<name>` wherever the perf
  section is read) and, while a profiler session runs, the same interval
  stands on the device trace's clock under the same name. On the served
  path the phases are a partition of the blocking thread's time, layer
  boundary by layer boundary (admit → commit_wait | encode → commit →
  upload → dispatch → route → pack → upload → dispatch → publish →
  readback[device_wait] → publish);
  the `metrics.trace` spans `sync_request`, `sync_round_flush` and
  `rows_round_apply` are their parents and carry the request's id. Phase
  names are lint-enforced (the graftlint registry pass) the same way
  metric names are.
- **memory gauges** — a throttled `jax.live_arrays()` sample maintains
  the live-array footprint and its high-water mark
  (`obs_live_arrays_bytes` / `obs_live_arrays_peak_bytes`); the engines
  publish their resident-state footprints (`rows_resident_bytes`,
  `engine_resident_bytes`, `sync_shard_resident_bytes{shard=...}`). All
  of it rides inside `metrics.snapshot()`, so every flight-recorder
  post-mortem embeds the memory picture at the time of the hang.

Analysis cost note: every new kernel signature is traced and lowered a
second time for `cost_analysis()`, and mode `full` adds an AOT
`lowered.compile()` for `memory_analysis()`. The jit call that follows
finds that executable in jax's own cache and does not compile again, so
the listener credits the analysis's backend compile to the dispatch (it
is the product's compile, made one call early). The default mode is
backend-aware (`AMTPU_PERFSCOPE=auto`): full analysis everywhere except
the tpu backend, which gets the trace-only cost analysis.
`AMTPU_PERFSCOPE=full` forces HBM sections on TPU too; `cost` forces
trace-only; `0` disables signature analysis entirely. Compile
*observation* (counts + attributed wall time) is listener-based — it
stays on in every mode.

Locking discipline: the store lock guards only dict arithmetic. Metric
emission, jax calls, and the AOT analysis all run outside it, so this
module adds no lock-order edge against the metrics store (the
lock-discipline pass scans utils/). A phase exit takes no lock at all:
each thread accumulates into a dict of its own, and the readers
(`phase_totals()`, `perf_snapshot()`, `reset()`) merge them.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from contextlib import contextmanager

import jax.profiler

log = logging.getLogger("automerge_tpu.perfscope")

#: Registered phase names for `phase()` — the cross-layer wall-time
#: rollup. The graftlint registry pass rejects unregistered literals at
#: phase() call sites, exactly like metric names (docs/OBSERVABILITY.md
#: "Performance plane").
PHASES: dict[str, str] = {
    "admit": "one ingress of the service up to its place in the pending "
             "round or the epoch buffer: wire columns, ghost check, append "
             "(sync/service.py); not the flush, not the park",
    "commit_wait": "a caller parked on its epoch ticket until the flush "
                   "that carried its entry resolves it (sync/epochs.py)",
    "encode": "round-frame decode, actor registration, budget precheck and "
              "the native delta encode "
              "(resident_rows._dispatch_round_frames)",
    "commit": "the encoded round committed to the host row mirror: growth, "
              "scatter triplets, dirty marks, dedup and padding "
              "(resident_rows)",
    "route": "the reconcile router, once a round and once a hash read "
             "(engine/dispatch.py reconcile_route); where it plans the "
             "fused route: used-size band scans over the dirty lanes and "
             "bucket planning against the link prices (plan_round)",
    "pack": "columnar batch/rows packing on the host (engine/pack.py) and "
            "the gather of dirty lanes from the host row mirror, or the "
            "plan of their gather on the device (_reconcile_lanes)",
    "upload": "host->device transfers of the resident engines (_to_dev)",
    "dispatch": "jitted kernel dispatch calls (metrics.dispatch_jit)",
    "device_wait": "explicit host barriers on in-flight device work "
                   "(block_until_ready), inside `readback` on the rows path",
    "readback": "device->host readbacks (hash reads, the trusted barrier)",
    "publish": "the service's tail: behind the engine's dispatch half "
               "admission scans, ledgers, read versions, notify queue, "
               "archive trigger; behind its collect half (`readback`) the "
               "round's counters, ticket resolve, handler gossip "
               "(sync/service.py)",
    "host_materialize": "interpretive apply + snapshot materialization "
                        "(frontend/materialize.py)",
    "sync_wire": "wire encode/decode of sync frames (sync/frames.py)",
    "fleet_hashes": "fleet-wide convergence reads: the sharded hash "
                    "fan-out incl. per-shard dirty-lane reconciles "
                    "(sync/sharded_service.py)",
    "shard_fanout": "the sharded service's fan-out at the exit of a "
                    "batch(): every shard's flush, one after another, "
                    "around the shards' own phases "
                    "(sync/sharded_service.py)",
    "span_merge": "span-granularity text-merge placement: run placement "
                  "walks + ElemList splices (core/textspans.py)",
}

#: seconds between jax.live_arrays() footprint samples (the walk is
#: O(live arrays); dispatch sites sample opportunistically)
LIVE_SAMPLE_INTERVAL_S = 0.5

_UNATTRIBUTED = "(unattributed)"

_tls = threading.local()


def _analysis_mode() -> str:
    """"full" (cost + memory analysis) | "cost" | "off". The default is
    backend-aware: "full" everywhere EXCEPT the tpu backend, which gets
    "cost". Set AMTPU_PERFSCOPE=full explicitly to get HBM sections on
    TPU runs."""
    raw = os.environ.get("AMTPU_PERFSCOPE", "auto").strip().lower()
    if raw in ("0", "off", "none", "false"):
        return "off"
    if raw in ("cost", "full"):
        return raw
    try:
        import jax
        backend = jax.default_backend()
    except Exception:
        backend = "cpu"
    return "cost" if backend == "tpu" else "full"


class _KernelStats:
    __slots__ = ("dispatches", "compiles", "compile_s", "trace_s",
                 "lower_s", "signatures")

    def __init__(self):
        self.dispatches = 0
        self.compiles = 0        # dispatch windows that observed a compile
        self.compile_s = 0.0     # backend compile seconds
        self.trace_s = 0.0       # jaxpr trace seconds
        self.lower_s = 0.0       # jaxpr -> MLIR lowering seconds
        self.signatures: set = set()


class _Store:
    def __init__(self):
        self.lock = threading.Lock()
        self.kernels: dict[str, _KernelStats] = {}
        # phase accumulators, name -> [seconds, count]: one dict a live
        # thread (written by its owner alone, without this lock) and the
        # folded totals of the threads that have exited
        self.thread_phases: list[tuple[threading.Thread, dict]] = []
        self.retired_phases: dict[str, list] = {}
        self.live_bytes = 0
        self.live_peak = 0
        self._last_live = 0.0

    def kernel(self, name: str) -> _KernelStats:
        st = self.kernels.get(name)
        if st is None:
            st = self.kernels[name] = _KernelStats()
        return st


_store = _Store()

# Analysis results survive metrics.reset(): XLA's answer for a compiled
# kernel variant does not change between bench configs, and per-config
# snapshots must still carry cost/memory rows for kernels compiled in an
# earlier config. kernel -> {"cost": {...}|None, "memory": {...}|None}
_analysis_lock = threading.Lock()
_analysis: dict[str, dict] = {}


class _Marker:
    """Per-dispatch compile-event accumulator (thread-local; no lock)."""
    __slots__ = ("kernel", "events", "compile_s", "trace_s", "lower_s")

    def __init__(self, kernel: str):
        self.kernel = kernel
        self.events = 0
        self.compile_s = 0.0
        self.trace_s = 0.0
        self.lower_s = 0.0

    def note(self, event: str, seconds: float) -> None:
        self.events += 1
        if event.endswith("backend_compile_duration"):
            self.compile_s += seconds
        elif event.endswith("jaxpr_trace_duration"):
            self.trace_s += seconds
        else:
            self.lower_s += seconds


# ---------------------------------------------------------------------------
# jax.monitoring listener (compile-event ground truth)


_installed = False
_install_lock = threading.Lock()


def _on_event_duration(name: str, seconds: float, **kw) -> None:
    if not name.startswith("/jax/core/compile"):
        return
    analyzing = getattr(_tls, "analyzing", None)
    if analyzing is not None:
        # our own out-of-band analysis: its trace and lowering are extra
        # work, not a product retrace. Its backend compile is the one the
        # jit call then reuses from jax's executable cache — without this
        # credit a kernel analyzed in mode `full` reports compile_s == 0
        if name.endswith("backend_compile_duration"):
            analyzing.note(name, seconds)
        return
    stack = getattr(_tls, "stack", None)
    if stack:
        stack[-1].note(name, seconds)
        return
    # a compile outside any dispatch_jit window (e.g. bench's own jits):
    # still worth counting, under a reserved bucket
    with _store.lock:
        st = _store.kernel(_UNATTRIBUTED)
        if name.endswith("backend_compile_duration"):
            st.compiles += 1
            st.compile_s += seconds
        elif name.endswith("jaxpr_trace_duration"):
            st.trace_s += seconds
        else:
            st.lower_s += seconds


def ensure_installed() -> bool:
    """Register the jax.monitoring compile-duration listener (idempotent).
    Returns False when jax.monitoring is unavailable."""
    global _installed
    if _installed:
        return True
    with _install_lock:
        if _installed:
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                _on_event_duration)
        except Exception:
            return False
        _installed = True
    return True


# ---------------------------------------------------------------------------
# per-dispatch accounting (driven by metrics.dispatch_jit)


def _signature(args, kwargs) -> tuple:
    """Abstract call signature: shapes/dtypes for array-likes, values for
    hashable statics. Two calls with equal signatures hit the same jit
    cache entry (modulo weak types — close enough to gate the one-time
    analysis)."""
    def one(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return ("a", tuple(shape), str(dtype))
        if isinstance(x, dict):
            # a pytree of arrays (apply_doc's batch): its leaves' shapes,
            # never its repr — that would read every array back
            return ("d", tuple((k, one(v)) for k, v in sorted(x.items())))
        try:
            hash(x)
            return ("s", x)
        except TypeError:
            return ("r", repr(x)[:80])
    return (tuple(one(a) for a in args),
            tuple((k, one(v)) for k, v in sorted(kwargs.items())))


_install_warned = False


def dispatch_begin(kernel: str, fn, args: tuple, kwargs: dict):
    """Open a dispatch window: arm the listener, run the one-time
    signature analysis when this (kernel, signature) is new, and push the
    attribution marker. Returns the marker for dispatch_end()."""
    global _install_warned
    if not ensure_installed() and not _install_warned:
        _install_warned = True
        log.warning(
            "jax.monitoring compile listener unavailable — retrace "
            "detection and compile telemetry are degraded to zero "
            "(engine_kernels_retraced will not fire on this process)")
    try:
        sig = _signature(args, kwargs)
    except Exception:
        sig = None
    marker = _Marker(kernel)
    if sig is not None:
        with _store.lock:
            st = _store.kernel(kernel)
            new = sig not in st.signatures
            if new:
                st.signatures.add(sig)
        if new:
            # BEFORE the real call: donated input buffers are still live
            _analyze(kernel, fn, args, kwargs, marker)
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(marker)
    return marker


def dispatch_end(marker) -> bool:
    """Close a dispatch window. Folds the marker's compile events into the
    store and returns True when the dispatch compiled (a jit cache miss —
    the ground truth behind `engine_kernels_retraced`)."""
    stack = getattr(_tls, "stack", None)
    if stack is not None:
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is marker:
                del stack[i]
                break
    compiled = marker.events > 0
    with _store.lock:
        st = _store.kernel(marker.kernel)
        st.dispatches += 1
        if compiled:
            st.compiles += 1
            st.compile_s += marker.compile_s
            st.trace_s += marker.trace_s
            st.lower_s += marker.lower_s
    if compiled:
        from . import metrics
        metrics.add_time("engine_kernel_compile",
                         marker.compile_s + marker.trace_s + marker.lower_s,
                         kernel=marker.kernel)
    sample_live_arrays()
    return compiled


@contextmanager
def _analyzing(marker):
    prev = getattr(_tls, "analyzing", None)
    _tls.analyzing = marker
    try:
        yield
    finally:
        _tls.analyzing = prev


def _memory_dict(stats) -> dict | None:
    out = {}
    for attr, section in (("argument_size_in_bytes", "argument"),
                          ("output_size_in_bytes", "output"),
                          ("temp_size_in_bytes", "temp"),
                          ("alias_size_in_bytes", "alias"),
                          ("generated_code_size_in_bytes", "code")):
        v = getattr(stats, attr, None)
        if v is not None:
            out[section] = int(v)
    return out or None


def _cost_dict(raw) -> dict | None:
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else None
    if not isinstance(raw, dict):
        return None
    out = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals")):
        v = raw.get(key)
        if isinstance(v, (int, float)) and v >= 0:
            out[name] = float(v)
    return out or None


def _analyze(kernel: str, fn, args: tuple, kwargs: dict, marker) -> None:
    """One-time per (kernel, signature): XLA cost analysis from the traced
    lowering and (mode `full`) HBM section sizes from an AOT compile.
    Best-effort — a kernel that cannot be lowered out of band (non-jit
    callable, exotic statics) simply has no cost/memory rows."""
    mode = _analysis_mode()
    if mode == "off":
        return
    lower = getattr(fn, "lower", None)
    if not callable(lower):
        return
    cost = memory = None
    try:
        with _analyzing(marker):
            lowered = lower(*args, **kwargs)
            try:
                cost = _cost_dict(lowered.cost_analysis())
            except Exception:
                cost = None
            if mode == "full":
                compiled = lowered.compile()
                try:
                    c2 = _cost_dict(compiled.cost_analysis())
                    if c2:
                        cost = c2   # post-optimization numbers when available
                except Exception:
                    pass
                try:
                    memory = _memory_dict(compiled.memory_analysis())
                except Exception:
                    memory = None
    except Exception as e:
        log.debug("perfscope analysis failed for %r: %r", kernel, e)
        return
    with _analysis_lock:
        entry = _analysis.setdefault(kernel, {})
        if cost:
            entry["cost"] = cost
        if memory:
            entry["memory"] = memory
    from . import metrics
    if cost:
        if "flops" in cost:
            metrics.gauge("engine_kernel_flops", cost["flops"],
                          kernel=kernel)
        if "bytes_accessed" in cost:
            metrics.gauge("engine_kernel_bytes_accessed",
                          cost["bytes_accessed"], kernel=kernel)
    if memory:
        for section, v in memory.items():
            metrics.gauge("engine_kernel_hbm_bytes", v, kernel=kernel,
                          section=section)


# ---------------------------------------------------------------------------
# phase attribution


def _fold(into: dict, acc: dict) -> None:
    # list(): the owner may insert a name while this runs
    for name, (s, c) in list(acc.items()):
        e = into.get(name)
        if e is None:
            into[name] = [s, c]
        else:
            e[0] += s
            e[1] += c


def _thread_phases() -> dict:
    """Register and return the calling thread's accumulator (its first
    phase exit). Accumulators of threads that have exited are folded
    into the retired totals here, so respawning flusher threads do not
    grow the registry."""
    acc: dict = {}
    with _store.lock:
        live = []
        for t, a in _store.thread_phases:
            if t.is_alive():
                live.append((t, a))
            else:
                _fold(_store.retired_phases, a)
        live.append((threading.current_thread(), acc))
        _store.thread_phases = live
    _tls.phases = acc
    return acc


def _merged_phases() -> dict[str, list]:
    """name -> [seconds, count] over every thread. Call with the store
    lock held."""
    out = {n: list(e) for n, e in _store.retired_phases.items()}
    for _t, acc in _store.thread_phases:
        _fold(out, acc)
    return out


# bound once: a phase entry/exit is a per-admission cost, and each
# attribute lookup it saves is some 3 % of it
_Annotation = jax.profiler.TraceAnnotation
_annotation_init = _Annotation.__init__
_annotation_enter = _Annotation.__enter__
_annotation_exit = _Annotation.__exit__
_now = time.perf_counter


class phase(_Annotation):
    """`with phase(name):` accumulates wall time under one of the
    registered PHASES and holds a `jax.profiler.TraceAnnotation(name)`
    for the same interval, so a phase has the same name in the counters
    and on the profiler's timeline. Cheap enough for a per-admission
    site: two perf_counter reads, the annotation (a no-op without a
    profiler session), one thread-local dict update, no lock. On the
    served path phases are placed as a partition; elsewhere a nested
    phase counts in both."""

    __slots__ = ("_name", "_t0")

    def __init__(self, name: str):
        _annotation_init(self, name)
        self._name = name

    def __enter__(self):
        _annotation_enter(self)
        self._t0 = _now()

    def __exit__(self, exc_type, exc, tb):
        dt = _now() - self._t0
        _annotation_exit(self, exc_type, exc, tb)
        try:
            acc = _tls.phases
        except AttributeError:
            acc = _thread_phases()
        e = acc.get(self._name)
        if e is None:
            acc[self._name] = [dt, 1]
        else:
            e[0] += dt
            e[1] += 1


def phase_totals() -> dict[str, float]:
    """Accumulated seconds per phase since the last reset, over every
    thread — a cheap point-in-time read. The op-lifecycle plane
    (utils/oplag.py) snapshots this around a round flush and attributes
    the delta (pack/dispatch/device_wait) to the sampled ops that rode
    the round."""
    with _store.lock:
        return {n: e[0] for n, e in _merged_phases().items()}


def phased(name: str):
    """Decorator form of phase() for whole-function attribution (the pack
    entry points in engine/pack.py). Same lint discipline: the name
    literal at the decoration site must be a registered PHASE."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            # wrapper plumbing: the literal is checked at @phased("...")
            # decoration sites, not here
            with phase(name):   # graftlint: disable=phase-dynamic
                return fn(*args, **kwargs)
        return wrapped
    return deco


# ---------------------------------------------------------------------------
# memory gauges


def sample_live_arrays(force: bool = False) -> int | None:
    """Throttled live-array footprint sample; maintains the high-water
    mark. Returns the sampled byte total (None when throttled or jax is
    unavailable)."""
    now = time.monotonic()
    with _store.lock:
        if not force and now - _store._last_live < LIVE_SAMPLE_INTERVAL_S:
            return None
        _store._last_live = now
    try:
        import jax
        total = sum(int(getattr(a, "nbytes", 0) or 0)
                    for a in jax.live_arrays())
    except Exception:
        return None
    with _store.lock:
        _store.live_bytes = total
        if total > _store.live_peak:
            _store.live_peak = total
        peak = _store.live_peak
    from . import metrics
    metrics.gauge("obs_live_arrays_bytes", total)
    metrics.gauge("obs_live_arrays_peak_bytes", peak)
    return total


# ---------------------------------------------------------------------------
# snapshot / reset


def perf_snapshot() -> dict | None:
    """The `perf` section `metrics.snapshot()` embeds: per-kernel compile
    telemetry (counts, attributed seconds, XLA cost, HBM sections),
    cross-layer phase rollup, and the live-array footprint. None when
    nothing has been recorded since the last reset (so an untouched
    process still snapshots to `{}`)."""
    with _store.lock:
        kernels = {
            k: {"dispatches": st.dispatches,
                "compiles": st.compiles,
                "compile_s": round(st.compile_s, 6),
                "trace_s": round(st.trace_s, 6),
                "lower_s": round(st.lower_s, 6)}
            for k, st in _store.kernels.items()
            # idle entries (kept across reset() only for their signature
            # memory) stay out of the per-run snapshot
            if st.dispatches or st.compiles or st.compile_s
            or st.trace_s or st.lower_s}
        merged = _merged_phases()
        if not kernels and not merged and not _store.live_peak:
            return None
        phases = {n: {"s": round(s, 6), "count": c}
                  for n, (s, c) in merged.items()}
        memory = None
        if _store.live_peak:
            memory = {"live_array_bytes": _store.live_bytes,
                      "live_array_peak_bytes": _store.live_peak}
    with _analysis_lock:
        for k, entry in _analysis.items():
            if k in kernels:
                if entry.get("cost"):
                    kernels[k]["cost"] = dict(entry["cost"])
                if entry.get("memory"):
                    kernels[k]["memory"] = dict(entry["memory"])
    out: dict = {"kernels": kernels}
    if phases:
        out["phases"] = phases
    if memory:
        out["memory"] = memory
    return out


def reset() -> None:
    """Clear per-run counters/phases/footprint (metrics.reset() calls
    this). The per-kernel signature sets and cached XLA analyses survive:
    the jit caches they mirror are process-lived, and clearing them would
    re-run the (compile-costed) analysis every bench config."""
    with _store.lock:
        for st in _store.kernels.values():
            st.dispatches = 0
            st.compiles = 0
            st.compile_s = 0.0
            st.trace_s = 0.0
            st.lower_s = 0.0
        _store.kernels = {k: st for k, st in _store.kernels.items()
                          if st.signatures}
        _store.retired_phases.clear()
        for _t, acc in _store.thread_phases:
            acc.clear()     # in place: the owner keeps writing into it
        _store.live_bytes = 0
        _store.live_peak = 0
        _store._last_live = 0.0
