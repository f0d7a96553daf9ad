"""Compile observatory: every XLA recompile attributed, priced, explained.

The goodput ledger (r15) can say "the first dispatch burned a minute in
``compile``" and the memory observatory prices the compile workspace —
but nothing can answer the question an elastic mesh change, a shape
drift, or a cold persistent cache actually raises: **which function
recompiled, why, and should the cache have absorbed it?**  Restart-based
elasticity pays an XLA compile on every membership change; whether that
compile is a disk read (warm persistent cache) or minutes of HLO work is
the difference ElasWave-style live resharding and restart-vs-ride-out
pricing both need made observable.  Four pieces:

:class:`JitScope` (process singleton, :func:`scope`)
    The per-process compile ledger.  :func:`install` registers ONE pair
    of ``jax.monitoring`` listeners — the duration stream
    (``/jax/core/compile/*``: jaxpr trace, MLIR lowering, backend
    compile) and the event stream (``/jax/compilation_cache/
    cache_hits|cache_misses``) — accumulated per thread so concurrent
    dispatches attribute their own compile work.  :func:`watch` wraps a
    jitted callable; on each call the wrapper snapshots the thread's
    counters, and a nonzero delta means THIS call compiled: the scope
    records a **compile event** — function name, measured compile
    seconds, persistent-cache hit/miss, and a **trigger** classified by
    diffing the call's abstract signature (per-leaf shape/dtype/
    sharding spec/mesh fingerprint + caller-declared statics like
    ``donate``) against the last-seen signature for that call site:

    ``first-trace``            no prior signature (a cold call site)
    ``persistent-cache-miss``  no prior signature, but the persistent
                               cache was enabled and warm was EXPECTED
                               (restart / non-empty cache dir at boot)
                               and the call still missed — the event
                               the cache-cold sentinel exists for
    ``mesh-change``            the sharding meshes differ (an elastic
                               resize recompiling the world)
    ``arg-shape-delta``        leaf shapes moved (data shape drift)
    ``dtype-delta``            leaf dtypes moved
    ``sharding-delta``         same mesh, different partition specs
    ``donation-mismatch``      only the caller-declared statics moved
                               (e.g. the donate flag)
    ``retrace``                signature-identical retrace (an
                               in-process cache drop, ``clear_caches``)

    Events are spans too (``jitscope.compile``, fn/trigger/cache in the
    attrs) so they land in the flight-recorder ring, every incident
    dump, and the merged Perfetto timeline.

**Dispatch-stall probe**
    A watched call that blocks the host longer than
    ``DLROVER_TPU_JITSCOPE_STALL_MS`` while compile work landed in its
    window emits a ``jitscope.dispatch_stall`` span; a daemon thread
    polls the in-flight registry so a compile STILL in progress drops a
    ``jitscope.stall_detected`` event into the recorder — evidence an
    incident dump captures mid-compile, before the dispatch returns.

**The digest channel**
    ``js_*`` keys (cumulative, :data:`DIGEST_MERGE` rules) ride the
    rank-digest-file -> agent-heartbeat channel into
    ``master/timeseries.py`` (``node<N>.compile.*`` series +
    ``job.compile.s`` / ``job.compile.hit_ratio`` rollups), the
    ``/compile`` dashboard view, and ``/metrics`` gauges.

``CompileSentinel`` (``observability/sentinel.py``)
    watches the rollups: compile seconds per window breaching EWMA+MAD
    bounds opens ``recompile_storm``; a node that expected a warm
    persistent cache but missed opens ``cache_cold`` — both
    ``phase=compile``, finalized with the culprit's recent compile
    events embedded (function + trigger) from the flight dumps.

Chaos: :data:`COMPILE_POINT` fires inside every detected compile
window, so a seeded DELAY is injected compile seconds — the
deterministic storm the ``cache_cold`` drill scenario prices.

Everything is guarded: a broken observatory can never break a dispatch,
and ``DLROVER_TPU_JITSCOPE=0`` turns every hook into a flag check.
"""

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from dlrover_tpu.common import envs
from dlrover_tpu.common.log import logger

#: chaos injection point: fires inside every detected compile window
#: (after the dispatch, while the window is still open), so a seeded
#: DELAY fault IS injected compile time — the deterministic
#: recompile-storm the drills price.
COMPILE_POINT = "jitscope.compile"

#: the trigger taxonomy, classification priority order
TRIGGERS: Tuple[str, ...] = (
    "first-trace",
    "persistent-cache-miss",
    "mesh-change",
    "arg-shape-delta",
    "dtype-delta",
    "sharding-delta",
    "donation-mismatch",
    "retrace",
)

#: digest-key schema (flat floats riding ``comm.HeartBeat.digest``).
#: All cumulative except the markers; the agent merges rank files per
#: :data:`DIGEST_MERGE` and the master differentiates across ``js_seq``
#: advances.
DIGEST_PREFIX = "js_"

#: digest key -> merge rule across one host's rank files
#: (``elastic_agent._collect_digest``): "max" | "min" | "sum".
#: Counters SUM (node totals; the hit ratio derives from the sums),
#: markers take max (newest event ts; warm/cache are per-host flags).
DIGEST_MERGE: Dict[str, str] = {
    "js_ts": "max",
    "js_boot": "max",
    "js_seq": "sum",
    "js_compile_s": "sum",
    "js_hits": "sum",
    "js_misses": "sum",
    "js_stalls": "sum",
    "js_warm": "max",
    "js_cache": "max",
}


def enabled() -> bool:
    return envs.get_bool("DLROVER_TPU_JITSCOPE")


# ---------------------------------------------------------------------------
# jax.monitoring listeners: per-thread + process-total compile counters.
# Registered once per process (jax keeps listeners forever); they write
# to module-level accumulators so scope resets never re-register.
# ---------------------------------------------------------------------------

#: duration events that count as compile work (tracing + lowering +
#: backend compile; cache retrieval rides backend_compile already)
_COMPILE_DURATION_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class _Counters(threading.local):
    """Per-thread compile accumulators (synchronous jit dispatch traces
    and compiles in the calling thread, so a watched call's delta is
    exactly its own compile work)."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0


_tls = _Counters()
_totals_mu = threading.Lock()
_TOTALS = {"compile_s": 0.0, "hits": 0, "misses": 0}
_installed = False
_install_mu = threading.Lock()


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    if event in _COMPILE_DURATION_EVENTS and duration > 0:
        _tls.compile_s += duration
        with _totals_mu:
            _TOTALS["compile_s"] += duration


def _on_event(event: str, **_kw: Any) -> None:
    if event == _CACHE_HIT_EVENT:
        _tls.hits += 1
        with _totals_mu:
            _TOTALS["hits"] += 1
    elif event == _CACHE_MISS_EVENT:
        _tls.misses += 1
        with _totals_mu:
            _TOTALS["misses"] += 1


_install_attempted = False


def install() -> bool:
    """Register the ``jax.monitoring`` listeners (idempotent; returns
    whether the full stream is live).  Called from the worker
    bootstrap and lazily by the first :func:`watch`.  Registration is
    attempted ONCE per process and each listener is guarded on its own
    — jax keeps listeners forever, so a partial failure must never be
    retried (stacked duplicate listeners would multiply every compile
    second)."""
    global _installed, _install_attempted
    if _install_attempted:
        return _installed
    with _install_mu:
        if _install_attempted:
            return _installed
        _install_attempted = True
        dur_ok = ev_ok = False
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
            dur_ok = True
        except Exception as e:  # noqa: BLE001 - observability must not
            # break jax import-time quirks
            logger.warning("jitscope duration listener unavailable: %s", e)
        try:
            from jax import monitoring

            monitoring.register_event_listener(_on_event)
            ev_ok = True
        except Exception as e:  # noqa: BLE001
            logger.warning("jitscope event listener unavailable: %s", e)
        _installed = dur_ok and ev_ok
    return _installed


def _thread_counters() -> Tuple[float, int, int]:
    return _tls.compile_s, _tls.hits, _tls.misses


def totals() -> Dict[str, float]:
    """Process-wide compile counters (all threads, watched or not)."""
    with _totals_mu:
        return dict(_TOTALS)


# ---------------------------------------------------------------------------
# Abstract signatures + trigger classification.
# ---------------------------------------------------------------------------


def _mesh_fingerprint(sharding: Any) -> str:
    mesh = getattr(sharding, "mesh", None)
    if mesh is None:
        return type(sharding).__name__
    try:
        shape = tuple(sorted((str(k), int(v))
                             for k, v in dict(mesh.shape).items()))
        ids = getattr(mesh, "device_ids", None)
        count = (
            int(ids.size) if ids is not None
            else len(getattr(mesh, "devices", []) or [])
        )
        return f"{shape}x{count}"
    except Exception:  # noqa: BLE001 - a mesh we cannot fingerprint
        return "mesh?"


def signature_of(args: tuple, kwargs: dict,
                 static: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The abstract signature of one call: per-leaf shape/dtype/
    partition-spec tuples, the set of mesh fingerprints, and the
    caller-declared statics (donation flags etc).  Computed ONLY when a
    compile was detected — never on the cached hot path."""
    import jax

    shapes: List[Tuple] = []
    dtypes: List[str] = []
    specs: List[str] = []
    meshes: set = set()
    for leaf in jax.tree.leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            shapes.append((type(leaf).__name__,))
            dtypes.append(type(leaf).__name__)
            specs.append("")
            continue
        shapes.append(tuple(shape))
        dtypes.append(str(getattr(leaf, "dtype", "")))
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            specs.append("")
        else:
            specs.append(str(getattr(sharding, "spec", "")))
            meshes.add(_mesh_fingerprint(sharding))
    return {
        "shapes": tuple(shapes),
        "dtypes": tuple(dtypes),
        "specs": tuple(specs),
        "meshes": tuple(sorted(meshes)),
        "static": dict(static or {}),
    }


def classify_trigger(prev: Optional[Dict[str, Any]],
                     cur: Dict[str, Any],
                     missed: bool,
                     cache_enabled: bool,
                     warm_expected: bool) -> str:
    """Why did this call compile?  Diff against the call site's
    last-seen signature; a cold call site is ``first-trace`` unless the
    persistent cache was supposed to absorb it and did not."""
    if prev is None:
        if missed and cache_enabled and warm_expected:
            return "persistent-cache-miss"
        return "first-trace"
    if prev["meshes"] != cur["meshes"]:
        return "mesh-change"
    if prev["shapes"] != cur["shapes"]:
        return "arg-shape-delta"
    if prev["dtypes"] != cur["dtypes"]:
        return "dtype-delta"
    if prev["specs"] != cur["specs"]:
        return "sharding-delta"
    if prev["static"] != cur["static"]:
        return "donation-mismatch"
    if missed and cache_enabled:
        return "persistent-cache-miss"
    return "retrace"


# ---------------------------------------------------------------------------
# The process scope.
# ---------------------------------------------------------------------------


class JitScope:
    """Per-process compile ledger: bounded event ring, per-call-site
    last-seen signatures, stall bookkeeping, the digest.  One instance
    per process (see :func:`scope`); tests may build private ones."""

    def __init__(self, warm_expected: Optional[bool] = None,
                 cache_enabled: Optional[bool] = None):
        self._mu = threading.Lock()
        # boot marker: lets the master distinguish "this process
        # restarted" from "more events landed" even when the new
        # boot's event count EXCEEDS the dead boot's (cross-boot
        # deltas were the gp_seq/mm_ts bug class of r15/r17)
        self._boot = time.time()
        self._events: List[Dict[str, Any]] = []
        self._cap = max(16, envs.get_int("DLROVER_TPU_JITSCOPE_EVENTS"))
        # call-site name -> last-seen signature (updated on compiles)
        self._last_sig: Dict[str, Dict[str, Any]] = {}
        self._compile_s = 0.0
        self._hits = 0
        self._misses = 0
        self._stalls = 0
        self._seq = 0
        self._last_ts = 0.0
        self._last_event: Optional[Dict[str, Any]] = None
        if warm_expected is None or cache_enabled is None:
            info = _cache_info()
            if warm_expected is None:
                warm_expected = bool(
                    info.get("entries_at_boot", 0)
                ) or bool(info.get("restart", False))
            if cache_enabled is None:
                cache_enabled = bool(info.get("enabled", False))
        self.warm_expected = bool(warm_expected)
        self.cache_enabled = bool(cache_enabled)

    # -- recording ----------------------------------------------------------

    def record_compile(
        self,
        name: str,
        signature: Dict[str, Any],
        compile_s: float,
        hits: int,
        misses: int,
        start_ts: float,
        end_ts: float,
        wall_s: float,
    ) -> Dict[str, Any]:
        """One detected compile on a watched call site: classify the
        trigger, append the event, emit the span.  Returns the event."""
        with self._mu:
            prev = self._last_sig.get(name)
            trigger = classify_trigger(
                prev, signature, misses > 0,
                self.cache_enabled, self.warm_expected,
            )
            self._last_sig[name] = signature
            # a mixed window (sub-ops hit, the main program missed)
            # is a MISS: something still had to compile
            cache = (
                "off" if not self.cache_enabled
                else "miss" if misses > 0
                else "hit" if hits > 0
                else "uncached"  # below the cache's min-compile floor
            )
            event = {
                "ts": round(end_ts, 6),
                "fn": name,
                "trigger": trigger,
                "cache": cache,
                "compile_s": round(compile_s, 6),
                "dispatch_s": round(wall_s, 6),
            }
            self._events.append(event)
            del self._events[:-self._cap]
            self._compile_s += compile_s
            self._hits += hits
            self._misses += misses
            self._seq += 1
            self._last_ts = end_ts
            self._last_event = event
        try:
            from dlrover_tpu.observability import metrics as obs_metrics

            reg = obs_metrics.registry()
            reg.counter_inc(
                "dlrover_tpu_compile_seconds_total", compile_s,
                help=obs_metrics._help(
                    "dlrover_tpu_compile_seconds_total"
                ),
                fn=name,
            )
            reg.counter_inc(
                "dlrover_tpu_recompile_total",
                help=obs_metrics._help("dlrover_tpu_recompile_total"),
                fn=name, trigger=trigger,
            )
        except Exception:  # noqa: BLE001 - metrics must not break
            pass  # a dispatch
        _emit_span(
            "jitscope.compile", start_ts, end_ts,
            {"fn": name, "trigger": trigger, "cache": cache,
             "compile_s": round(compile_s, 6)},
        )
        return event

    def record_stall(self, name: str, start_ts: float, end_ts: float,
                     compile_s: float) -> None:
        """A watched call that blocked the host past the stall
        threshold while compile work landed in its window."""
        with self._mu:
            self._stalls += 1
        try:
            from dlrover_tpu.observability import metrics as obs_metrics

            obs_metrics.registry().counter_inc(
                "dlrover_tpu_dispatch_stall_total",
                help=obs_metrics._help(
                    "dlrover_tpu_dispatch_stall_total"
                ),
                fn=name,
            )
        except Exception:  # noqa: BLE001 - metrics must not break
            pass  # a dispatch
        _emit_span(
            "jitscope.dispatch_stall", start_ts, end_ts,
            {"fn": name, "compile_s": round(compile_s, 6),
             "blocked_s": round(end_ts - start_ts, 6)},
        )

    # -- reading ------------------------------------------------------------

    @property
    def last_event(self) -> Optional[Dict[str, Any]]:
        with self._mu:
            return dict(self._last_event) if self._last_event else None

    def events(self) -> List[Dict[str, Any]]:
        with self._mu:
            return [dict(e) for e in self._events]

    def summary(self) -> Dict[str, Any]:
        with self._mu:
            events = [dict(e) for e in self._events]
            by_trigger: Dict[str, int] = {}
            by_fn: Dict[str, float] = {}
            for event in events:
                by_trigger[event["trigger"]] = by_trigger.get(
                    event["trigger"], 0
                ) + 1
                by_fn[event["fn"]] = by_fn.get(
                    event["fn"], 0.0
                ) + event["compile_s"]
            looked_up = self._hits + self._misses
            return {
                "events": self._seq,
                "compile_s": round(self._compile_s, 6),
                "cache_hits": self._hits,
                "cache_misses": self._misses,
                "cache_hit_ratio": (
                    round(self._hits / looked_up, 6)
                    if looked_up else None
                ),
                "cache_enabled": self.cache_enabled,
                "warm_expected": self.warm_expected,
                "stalls": self._stalls,
                "by_trigger": by_trigger,
                "compile_s_by_fn": {
                    fn: round(s, 6) for fn, s in by_fn.items()
                },
                "recent": events[-8:],
            }

    def digest(self) -> Dict[str, float]:
        """Flat cumulative account for the heartbeat digest channel;
        the master differentiates across ``js_seq`` advances."""
        with self._mu:
            return {
                "js_ts": round(self._last_ts, 6),
                "js_boot": round(self._boot, 3),
                "js_seq": float(self._seq),
                "js_compile_s": round(self._compile_s, 6),
                "js_hits": float(self._hits),
                "js_misses": float(self._misses),
                "js_stalls": float(self._stalls),
                "js_warm": 1.0 if self.warm_expected else 0.0,
                "js_cache": 1.0 if self.cache_enabled else 0.0,
            }


def merge_digest(digest: Dict[str, float],
                 rank_digest: Dict[str, Any]) -> None:
    """Merge one rank file's ``js_*`` keys into the host digest per
    :data:`DIGEST_MERGE` (called by ``elastic_agent._collect_digest``)."""
    for key, rule in DIGEST_MERGE.items():
        value = rank_digest.get(key)
        if value is None:
            continue
        value = float(value)
        if rule == "sum":
            digest[key] = digest.get(key, 0.0) + value
        elif rule == "min":
            digest[key] = (
                value if key not in digest else min(digest[key], value)
            )
        else:
            digest[key] = max(digest.get(key, 0.0), value)


# ---------------------------------------------------------------------------
# The watch wrapper + dispatch-stall probe.
# ---------------------------------------------------------------------------

#: thread ident -> {"name", "start_ts", "flagged"} for every watched
#: call currently blocking its host thread (the stall probe's registry)
_INFLIGHT: Dict[int, Dict[str, Any]] = {}
_inflight_mu = threading.Lock()


def inflight() -> List[Dict[str, Any]]:
    """Snapshot of watched calls currently in flight (name + age);
    incident dumps read this through the stall probe's events."""
    now = time.time()
    with _inflight_mu:
        return [
            {"fn": e["name"], "blocked_s": round(now - e["start_ts"], 3)}
            for e in _INFLIGHT.values()
        ]


class _StallProbe:
    """Daemon poller: a compile STILL in flight past the threshold
    drops a ``jitscope.stall_detected`` event into the flight recorder
    — evidence an incident dump can capture before the dispatch
    returns."""

    def __init__(self):
        self._started = False
        self._mu = threading.Lock()

    def ensure_started(self) -> None:
        if self._started:
            return
        with self._mu:
            if self._started:
                return
            self._started = True
            thread = threading.Thread(
                target=self._loop, daemon=True, name="jitscope-stall"
            )
            thread.start()

    def _loop(self) -> None:
        while True:
            threshold = _stall_s()
            time.sleep(max(0.05, threshold / 4 if threshold > 0 else 1.0))
            if threshold <= 0:
                continue
            now = time.time()
            flagged: List[Dict[str, Any]] = []
            with _inflight_mu:
                for entry in _INFLIGHT.values():
                    if (
                        not entry["flagged"]
                        and now - entry["start_ts"] >= threshold
                    ):
                        entry["flagged"] = True
                        flagged.append(dict(entry))
            for entry in flagged:
                try:
                    from dlrover_tpu.observability import flight_recorder

                    flight_recorder.on_event({
                        "ts": round(now, 6),
                        "type": "INSTANT",
                        "name": "jitscope.stall_detected",
                        "content": {
                            "fn": entry["name"],
                            "blocked_s": round(
                                now - entry["start_ts"], 3
                            ),
                        },
                    })
                except Exception as e:  # noqa: BLE001 - evidence is
                    logger.debug(  # best-effort
                        "jitscope stall event failed: %s", e
                    )


_STALL_PROBE = _StallProbe()


def _stall_s() -> float:
    return envs.get_float("DLROVER_TPU_JITSCOPE_STALL_MS") / 1000.0


class WatchedFunction:
    """The :func:`watch` wrapper: counts this thread's compile work
    around each call; a nonzero delta records a classified compile
    event on the scope.  The cached hot path costs two counter reads
    and one registry insert/remove."""

    def __init__(self, fn: Callable, name: str,
                 static: Optional[Dict[str, Any]] = None):
        self._fn = fn
        self.name = name
        self._static = dict(static or {})
        self.last_event: Optional[Dict[str, Any]] = None

    def lower(self, *args: Any, **kwargs: Any) -> Any:
        """The wrapped jit's ``lower``: a watched call site stays
        inspectable ahead of time (which kernels, which collectives)."""
        return self._fn.lower(*args, **kwargs)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if not enabled():
            return self._fn(*args, **kwargs)
        install()
        _STALL_PROBE.ensure_started()
        ident = threading.get_ident()
        with _inflight_mu:
            nested = ident in _INFLIGHT
        if nested:
            # nested watched call: the OUTER site owns this thread's
            # window — measuring here would double-count the compile
            # seconds and clobber the stall registry.  Dispatch OUTSIDE
            # the lock: a nested compile must not block every other
            # thread's registry insert (or the stall probe itself).
            return self._fn(*args, **kwargs)
        c0, h0, m0 = _thread_counters()
        start_ts = time.time()
        self.last_event = None
        with _inflight_mu:
            _INFLIGHT[ident] = {
                "name": self.name, "start_ts": start_ts, "flagged": False,
            }
        try:
            result = self._fn(*args, **kwargs)
        finally:
            with _inflight_mu:
                _INFLIGHT.pop(ident, None)
        c1, h1, m1 = _thread_counters()
        compile_s = c1 - c0
        if compile_s <= 0 and h1 == h0 and m1 == m0:
            return result  # the cached hot path
        try:
            # the chaos point fires INSIDE the still-open window: a
            # seeded DELAY is injected compile time, priced as exactly
            # the time the point call took (the sleep), nothing more
            from dlrover_tpu import chaos

            point_t0 = time.time()
            if chaos.point(COMPILE_POINT, fn=self.name) is not None:
                compile_s += time.time() - point_t0
        except Exception:  # noqa: BLE001 - chaos must not break dispatch
            pass
        end_ts = time.time()
        wall_s = end_ts - start_ts
        # nested sub-jit traces re-fire the jaxpr-trace duration inside
        # the outer program's, so the summed durations can slightly
        # exceed the dispatch wall — clamp: this call cannot have
        # compiled longer than it ran
        compile_s = min(compile_s, wall_s)
        try:
            signature = signature_of(args, kwargs, self._static)
            self.last_event = scope().record_compile(
                self.name, signature, compile_s,
                h1 - h0, m1 - m0, start_ts, end_ts, wall_s,
            )
            threshold = _stall_s()
            if threshold > 0 and wall_s >= threshold:
                scope().record_stall(
                    self.name, start_ts, end_ts, compile_s
                )
        except Exception as e:  # noqa: BLE001 - the observatory must
            # never break a dispatch
            logger.debug("jitscope record failed: %s", e)
        return result


def watch(fn: Callable, name: str,
          static: Optional[Dict[str, Any]] = None) -> WatchedFunction:
    """Wrap a jitted callable as a watched call site.  ``static``
    carries caller-declared compile-relevant flags (e.g.
    ``{"donate": True}``) so their changes classify as
    ``donation-mismatch``."""
    return WatchedFunction(fn, name, static=static)


# ---------------------------------------------------------------------------
# Span synthesis (events are known post-hoc, so the live trace.span
# context cannot carry them; records flow through the same export path).
# ---------------------------------------------------------------------------


def _emit_span(name: str, start_ts: float, end_ts: float,
               attrs: Dict[str, Any]) -> None:
    try:
        from dlrover_tpu.observability import trace

        if not trace.enabled():
            # tracing off: the flight recorder still gets the evidence
            from dlrover_tpu.observability import flight_recorder

            flight_recorder.on_span({
                "ts": round(start_ts, 6),
                "dur": round(max(0.0, end_ts - start_ts), 6),
                "name": name, "type": "SPAN", "kind": "internal",
                "trace_id": "", "span_id": "", "parent_span_id": "",
                "status": "ok", "attrs": attrs, "events": [],
            })
            return
        sp = trace.Span(
            name, trace.INTERNAL, trace.new_trace_id(),
            trace.new_span_id(), attrs=attrs,
        )
        sp.end()
        sp.start_ns, sp.end_ns = int(start_ts * 1e9), int(end_ts * 1e9)
        trace._export(sp)
    except Exception as e:  # noqa: BLE001 - telemetry must not break
        logger.debug("jitscope span emit failed: %s", e)


@contextlib.contextmanager
def persistent_cache_override(cache_dir: str,
                              min_compile_s: float = 0.0):
    """Point jax's persistent compile cache at ``cache_dir`` for the
    duration (drills, smokes, tests).  Handles the fiddly part in ONE
    place: jax memoizes "is the cache used" once per task at the first
    compile, so a process that compiled anything before the dir was
    configured must reset that marker — and again on exit so the
    restored config governs later compiles."""
    import jax

    def _reset_cache_marker() -> None:
        try:
            from jax._src import compilation_cache as _cc

            _cc.reset_cache()
        except Exception as e:  # noqa: BLE001 - private API best-effort
            logger.debug("compilation_cache reset unavailable: %s", e)

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_s
    )
    _reset_cache_marker()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min_s
        )
        _reset_cache_marker()


# ---------------------------------------------------------------------------
# Persistent-cache boot state (fed by trainer/bootstrap.py).
# ---------------------------------------------------------------------------


def _cache_info() -> Dict[str, Any]:
    try:
        from dlrover_tpu.trainer import bootstrap

        return bootstrap.compile_cache_info()
    except Exception:  # noqa: BLE001 - bootstrap not initialized
        return {}


_SCOPE: Optional[JitScope] = None
_SCOPE_MU = threading.Lock()


def scope() -> JitScope:
    """The process singleton every watched call writes to."""
    global _SCOPE
    if _SCOPE is None:
        with _SCOPE_MU:
            if _SCOPE is None:
                _SCOPE = JitScope()
    return _SCOPE


def reset_scope(warm_expected: Optional[bool] = None,
                cache_enabled: Optional[bool] = None) -> JitScope:
    """Replace the singleton (tests, per-boot drill isolation)."""
    global _SCOPE
    with _SCOPE_MU:
        _SCOPE = JitScope(
            warm_expected=warm_expected, cache_enabled=cache_enabled
        )
        return _SCOPE
