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
  boundary by layer boundary (admit → commit_wait | encode → [compact →
  encode →] commit →
  upload → dispatch → route → pack → upload → dispatch → publish →
  readback[device_wait] → publish);
  the `metrics.trace` spans `sync_request`, `sync_round_flush` and
  `rows_round_apply` are their parents and carry the request's id. Phase
  names are lint-enforced (the graftlint registry pass) the same way
  metric names are. Beside the phases, two accumulators under the same
  `phase.<name>` key: `gc`, every collection of the cyclic collector on
  the thread that runs it (a `gc.callbacks` hook, with its own
  annotation, nested in whatever phase it interrupts), and `unnamed`,
  what the phases leave of an outermost served span (`served`), where
  the serving thread's CPU time is read beside its wall time.
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
lock-discipline pass scans utils/). A phase exit and the gc hook take no
lock at all: each thread accumulates into a slot of its own, registered
by an atomic append, and the readers (`phase_totals()`,
`perf_snapshot()`, `gc_collections()`, `reset()`) merge them.
"""

from __future__ import annotations

import functools
import gc
import logging
import os
import resource
import threading
import time
from collections import deque
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
             "(sync/service.py); inside a batch() the whole body and the "
             "tally of its round, one entry a batch; not the flush, not "
             "the park",
    "commit_wait": "a caller parked on its epoch ticket until the flush "
                   "that carried its entry resolves it (sync/epochs.py)",
    "encode": "round-frame decode, actor registration, budget precheck, "
              "the admission guard's snapshot of log lengths and the "
              "native delta encode "
              "(resident_rows._dispatch_round_frames)",
    "compact": "the round's per-document compaction on the served path: "
               "the floors and pins of the documents the round would take "
               "past the resident caps, and their compaction "
               "(resident_rows._compact_over, inside the precheck, out of "
               "`encode`)",
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
    "dispatch": "jitted kernel dispatch calls with their bookkeeping: "
                "signature check, compile attribution, counters, flight "
                "record (metrics.dispatch_jit)",
    "device_wait": "explicit host barriers on in-flight device work "
                   "(block_until_ready), inside `readback` on the rows path",
    "readback": "device->host readbacks (hash reads, the trusted barrier)",
    "publish": "the service's bookkeeping around the engine: before it "
               "the round's flight record and the log lengths the "
               "admission scan reads back; behind the engine's dispatch "
               "half admission scans, ONE fold by tenant, ledgers, read "
               "versions, notify queue, archive trigger; behind its "
               "collect half (`readback`) the round's counters, ticket "
               "resolve, the dispatch ledger's round fold; after the "
               "lock, handler gossip (sync/service.py)",
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
    # not phase() sites: accumulators kept beside the phases, under the
    # same `phase.<name>` key of the perf section
    "gc": "the cyclic collector, every collection on the thread that "
          "runs it (a gc.callbacks hook, installed at import): nested in "
          "whatever phase it interrupts, as device_wait in readback",
    "unnamed": "what the phases leave of an outermost served span "
               "(`sync_request` on a caller's thread, `sync_round_flush` "
               "on the flusher's): its duration minus the phases on its "
               "thread inside it, minus the collector outside any phase",
}

#: phases that hold others on the served path (the shards' flushes, the
#: shards' hash reads): their time is a parent's, like a span's, and the
#: phases inside them are the partition
CONTAINERS = frozenset({"shard_fanout", "fleet_hashes"})

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
        # one _Slot a live thread (written by its owner alone, without
        # this lock) and the folded totals of the threads that have exited
        self.slots: list = []
        self.retired_phases: dict[str, list] = {}
        self.retired_gc_gens = [0, 0, 0]
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


class _Slot:
    """One thread's accumulators, written by that thread alone and with
    no lock (the gc hook runs inside any allocation, so it may take
    none); the readers merge them under the store lock. `acc` is the
    phases' {name: [seconds, count]}; `depth` counts the open phases
    that are not containers, and `top_s` the seconds of the outermost
    ones: what the phases cover of this thread's time. The collector's
    slots are apart from `acc`, which a phase exit may be updating when
    a collection starts."""

    __slots__ = ("thread", "acc", "depth", "top_s", "serving", "gc_s",
                 "gc_out_s", "gc_gens", "gc_t0", "gc_note")

    def __init__(self, thread):
        self.thread = thread
        self.acc: dict = {}
        self.depth = 0
        self.top_s = 0.0
        self.serving = 0
        self.gc_s = 0.0
        self.gc_out_s = 0.0        # the collector outside any phase
        self.gc_gens = [0, 0, 0]   # collections by generation
        self.gc_t0 = 0.0
        self.gc_note = None


# slots of threads not yet seen by a reader: appended without a lock (a
# deque's append is atomic), moved into the store by the next reader
_fresh: deque = deque()


def _thread_slot() -> _Slot:
    slot = _Slot(threading.current_thread())
    _tls.slot = slot
    _fresh.append(slot)
    return slot


def _fold(into: dict, acc: dict) -> None:
    # list(): the owner may insert a name while this runs
    for name, (s, c) in list(acc.items()):
        e = into.get(name)
        if e is None:
            into[name] = [s, c]
        else:
            e[0] += s
            e[1] += c


def _live_slots() -> list:
    """The slots of live threads; those of exited threads are folded
    into the retired totals, so respawning flusher threads do not grow
    the registry. Call with the store lock held."""
    while _fresh:
        _store.slots.append(_fresh.popleft())
    live = []
    for slot in _store.slots:
        if slot.thread.is_alive():
            live.append(slot)
        else:
            _fold(_store.retired_phases, slot.acc)
            _fold(_store.retired_phases, _gc_row(slot))
            for g, n in enumerate(slot.gc_gens):
                _store.retired_gc_gens[g] += n
    _store.slots = live
    return live


def _gc_row(slot: _Slot) -> dict:
    n = sum(slot.gc_gens)
    return {"gc": [slot.gc_s, n]} if n else {}


def _merged_phases() -> dict[str, list]:
    """name -> [seconds, count] over every thread, the collector's
    accumulator under `gc`. Call with the store lock held."""
    live = _live_slots()
    out = {n: list(e) for n, e in _store.retired_phases.items()}
    for slot in live:
        _fold(out, slot.acc)
        _fold(out, _gc_row(slot))
    return out


def gc_collections() -> list[int]:
    """Collections since the last reset, by generation, over every
    thread."""
    with _store.lock:
        live = _live_slots()
        gens = list(_store.retired_gc_gens)
        for slot in live:
            for g, n in enumerate(slot.gc_gens):
                gens[g] += n
    return gens


# bound once: a phase entry/exit is a per-call cost, and each attribute
# lookup it saves is some 3 % of it
_Annotation = jax.profiler.TraceAnnotation
_annotation_init = _Annotation.__init__
_annotation_enter = _Annotation.__enter__
_annotation_exit = _Annotation.__exit__
_now = time.perf_counter

#: a profiler annotation alone, no accumulator: a read that should stand
#: on the device trace's clock under its own name (metrics.snapshot)
annotate = _Annotation


class phase(_Annotation):
    """`with phase(name):` accumulates wall time under one of the
    registered PHASES and holds a `jax.profiler.TraceAnnotation(name)`
    for the same interval, so a phase has the same name in the counters
    and on the profiler's timeline. Cheap enough for a per-call site:
    two perf_counter reads, the annotation (a no-op without a profiler
    session), the thread's slot, no lock. On the served path phases are
    placed as a partition: a phase opened inside another (`device_wait`
    in `readback`) counts in both, and only the outermost in the time
    the phases cover (`top_s`); a CONTAINERS phase counts in neither
    depth nor cover, so the phases inside it are the partition."""

    __slots__ = ("_name", "_t0", "_slot", "_nest")

    def __init__(self, name: str):
        _annotation_init(self, name)
        self._name = name
        self._nest = name not in CONTAINERS

    def __enter__(self):
        _annotation_enter(self)
        try:
            slot = _tls.slot
        except AttributeError:
            slot = _thread_slot()
        self._slot = slot
        slot.depth += self._nest
        self._t0 = _now()

    def __exit__(self, exc_type, exc, tb):
        dt = _now() - self._t0
        _annotation_exit(self, exc_type, exc, tb)
        slot = self._slot
        if self._nest:
            slot.depth -= 1
            if not slot.depth:
                slot.top_s += dt
        acc = slot.acc
        e = acc.get(self._name)
        if e is None:
            acc[self._name] = [dt, 1]
        else:
            e[0] += dt
            e[1] += 1


def _on_gc(stage: str, info: dict) -> None:
    """gc.callbacks hook: every collection timed on the thread that runs
    it, under `gc`, and held as a `TraceAnnotation("gc")` for the same
    interval. Takes no lock and calls nothing that could: it runs inside
    whatever allocation set it off."""
    try:
        slot = _tls.slot
    except AttributeError:
        slot = _thread_slot()
    if stage == "start":
        note = slot.gc_note = _Annotation("gc")
        _annotation_enter(note)
        slot.gc_t0 = _now()
        return
    dt = _now() - slot.gc_t0
    _annotation_exit(slot.gc_note, None, None, None)
    slot.gc_note = None
    slot.gc_s += dt
    slot.gc_gens[info["generation"]] += 1
    if not slot.depth:
        slot.gc_out_s += dt


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)


_getrusage = resource.getrusage
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)  # Linux only
_WAITS = ("device_wait", "commit_wait")


def _waits(acc: dict) -> float:
    return sum(acc[w][0] for w in _WAITS if w in acc)


class served:
    """`with served():` around an outermost served span (`sync_request`
    on a caller's thread, `sync_round_flush` on the flusher's; opened
    inside another served span or inside a phase, it does nothing). At
    its exit the span's duration minus the phases that ran on its thread
    inside it, minus the collector outside any phase, goes to `unnamed`;
    and from one `getrusage(RUSAGE_THREAD)` at each end, the counters
    `sync_serve_cpu_us` (the thread's CPU time), `sync_serve_busy_us`
    (the wall time less its declared waits, `device_wait` and
    `commit_wait`) and `sync_serve_preempted` (involuntary context
    switches): on the CPU, or off it."""

    __slots__ = ("_slot", "_t0", "_top0", "_gc0", "_wait0", "_ru0")

    def __enter__(self):
        try:
            slot = _tls.slot
        except AttributeError:
            slot = _thread_slot()
        self._slot = slot
        slot.serving += 1
        if slot.serving > 1 or slot.depth:
            self._t0 = None
            return self
        self._top0 = slot.top_s
        self._gc0 = slot.gc_out_s
        self._wait0 = _waits(slot.acc)
        self._ru0 = (_getrusage(_RUSAGE_THREAD)
                     if _RUSAGE_THREAD is not None else None)
        self._t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb):
        slot = self._slot
        slot.serving -= 1
        if self._t0 is None:
            return False
        dt = _now() - self._t0
        ru1 = _getrusage(_RUSAGE_THREAD) if self._ru0 is not None else None
        unnamed = dt - (slot.top_s - self._top0) - (slot.gc_out_s - self._gc0)
        acc = slot.acc
        e = acc.get("unnamed")
        if e is None:
            acc["unnamed"] = [unnamed, 1]
        else:
            e[0] += unnamed
            e[1] += 1
        from . import metrics
        metrics.bump("sync_serve_busy_us",
                     round((dt - _waits(acc) + self._wait0) * 1e6))
        if ru1 is not None:
            ru0 = self._ru0
            metrics.bump("sync_serve_cpu_us", round(
                (ru1.ru_utime + ru1.ru_stime - ru0.ru_utime - ru0.ru_stime)
                * 1e6))
            metrics.bump("sync_serve_preempted", ru1.ru_nivcsw - ru0.ru_nivcsw)
        return False


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
        live = _live_slots()    # folds the exited threads first
        _store.retired_phases.clear()
        _store.retired_gc_gens = [0, 0, 0]
        for slot in live:
            # in place: the owner keeps writing into them. `top_s`,
            # `gc_out_s` and `depth` stay: a served span open across the
            # reset reads them as differences
            slot.acc.clear()
            slot.gc_s = 0.0
            slot.gc_gens[:] = [0, 0, 0]
        _store.live_bytes = 0
        _store.live_peak = 0
        _store._last_live = 0.0
