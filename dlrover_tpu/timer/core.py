"""Execution timer: Python facade over the native tpu_timer core.

TPU-native counterpart of the reference's xpu_timer stack (§2.6 of
SURVEY.md): the C++ core (native/tpu_timer/tpu_timer.cc, loaded via
ctypes) owns the event ring buffer, per-name aggregation, Prometheus
exposition, and — crucially — the hang watchdog, which keeps observing
even when the Python process is wedged in a stuck collective.  Metric
names keep xpu_timer's vocabulary (``XPU_TIMER_COMMON_HANG``,
``XPU_TIMER_KERNEL_*``) so reference dashboards/alerts port unchanged.

A pure-Python fallback implements the same API when the native library
is unavailable (no toolchain); the build is attempted on demand.
"""

import contextlib
import ctypes
import os
import subprocess
import threading
import time
from typing import Dict, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.common import envs

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_LIB_PATHS = [
    os.path.join(_REPO_ROOT, "native", "build", "libtpu_timer.so"),
    os.path.join(os.path.dirname(__file__), "libtpu_timer.so"),
]


def _direct_build(src_dir: str, build_dir: str) -> Optional[str]:
    """cmake-less fallback: the library is ONE translation unit, so a
    bare compiler invocation suffices (sandboxes ship g++ but often not
    cmake)."""
    import shutil

    cxx = next(
        (c for c in ("c++", "g++", "clang++") if shutil.which(c)), None
    )
    if cxx is None:
        return None
    out = os.path.join(build_dir, "libtpu_timer.so")
    try:
        os.makedirs(build_dir, exist_ok=True)
        subprocess.run(
            [
                cxx, "-std=c++17", "-O2", "-shared", "-fPIC",
                os.path.join(src_dir, "tpu_timer", "tpu_timer.cc"),
                "-o", out, "-lpthread",
            ],
            check=True, capture_output=True, timeout=300,
        )
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning("direct native timer build failed: %s", e)
        return None
    return out if os.path.exists(out) else None


def _try_build() -> Optional[str]:
    src_dir = os.path.join(_REPO_ROOT, "native")
    build_dir = os.path.join(src_dir, "build")
    if not os.path.exists(os.path.join(src_dir, "CMakeLists.txt")):
        return None
    try:
        subprocess.run(
            ["cmake", "-S", src_dir, "-B", build_dir],
            check=True, capture_output=True, timeout=120,
        )
        subprocess.run(
            ["cmake", "--build", build_dir],
            check=True, capture_output=True, timeout=300,
        )
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning(
            "cmake timer build failed (%s); trying a direct compile", e
        )
        return _direct_build(src_dir, build_dir)
    path = os.path.join(build_dir, "libtpu_timer.so")
    return path if os.path.exists(path) else None


def _load_native(allow_build: bool = False) -> Optional[ctypes.CDLL]:
    for path in _LIB_PATHS:
        if os.path.exists(path):
            try:
                return ctypes.CDLL(path)
            except OSError as e:
                logger.warning("failed to load %s: %s", path, e)
    if allow_build:
        # NEVER on the worker boot path — a cold cmake build would stall
        # rendezvous for minutes; callers opt in (tests, bench, tooling)
        built = _try_build()
        if built:
            try:
                return ctypes.CDLL(built)
            except OSError as e:  # pragma: no cover
                logger.warning("failed to load built lib: %s", e)
    return None


class _PyFallback:
    """Same API as the native core, minus the GIL-independent watchdog.

    Serves the same Prometheus exposition the native core does, on a
    loopback (127.0.0.1) HTTP server — bound AND fetched by numeric IP
    so DNS-less sandboxes (where resolving ``localhost`` fails with
    ``Servname not supported for ai_socktype``) still scrape cleanly.
    """

    def __init__(self):
        self._events = []
        self._aggs: Dict[str, list] = {}
        self._gauges: Dict[str, float] = {}
        self._last_activity = time.monotonic_ns()
        self._hang_timeout_ns = 0
        self._lock = threading.Lock()
        self._httpd = None

    def tt_init(self, port, hang_timeout_ms):
        self._hang_timeout_ns = hang_timeout_ms * 1_000_000
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        fallback = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # quiet
                pass

            def do_GET(self):  # noqa: N802
                body = fallback._exposition().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        try:
            self._httpd = ThreadingHTTPServer(
                ("127.0.0.1", max(0, int(port))), Handler
            )
        except OSError as e:
            logger.warning("fallback metrics server failed: %s", e)
            return -1
        threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="pyfallback-metrics",
        ).start()
        return self._httpd.server_address[1]

    def _exposition(self) -> str:
        """Mirror of the native core's page (same metric vocabulary, so
        dashboards/daemon scrapes cannot tell the backends apart)."""
        lines = []
        with self._lock:
            for name, value in sorted(self._gauges.items()):
                lines.append(f"{name} {value}")
            hang = self.tt_hang()
            lines.append(f"XPU_TIMER_COMMON_HANG {hang}")
            lines.append(
                "XPU_TIMER_SECONDS_SINCE_ACTIVITY "
                f"{self.tt_seconds_since_activity()}"
            )
            for name, (count, sum_ms, max_ms) in sorted(self._aggs.items()):
                avg = sum_ms / count if count else 0.0
                lines.append(
                    f'XPU_TIMER_KERNEL_COUNT{{name="{name}"}} {count}'
                )
                lines.append(
                    f'XPU_TIMER_KERNEL_SUM_MS{{name="{name}"}} {sum_ms}'
                )
                lines.append(
                    f'XPU_TIMER_KERNEL_MAX_MS{{name="{name}"}} {max_ms}'
                )
                lines.append(
                    f'XPU_TIMER_KERNEL_AVG_MS{{name="{name}"}} {avg}'
                )
        return "\n".join(lines) + "\n"

    def tt_record(self, name, start_ns, dur_ns, kind):
        name = name.decode() if isinstance(name, bytes) else name
        with self._lock:
            self._events.append((name, start_ns, dur_ns, kind))
            if len(self._events) > 65536:
                self._events.pop(0)
            agg = self._aggs.setdefault(name, [0, 0.0, 0.0])
            agg[0] += 1
            ms = dur_ns / 1e6
            agg[1] += ms
            agg[2] = max(agg[2], ms)
        self.tt_kick()

    def tt_kick(self):
        self._last_activity = time.monotonic_ns()

    def tt_set_gauge(self, name, value):
        name = name.decode() if isinstance(name, bytes) else name
        self._gauges[name] = value

    def tt_hang(self):
        if self._hang_timeout_ns <= 0:
            return 0
        return int(
            time.monotonic_ns() - self._last_activity > self._hang_timeout_ns
        )

    def tt_seconds_since_activity(self):
        return (time.monotonic_ns() - self._last_activity) // 1_000_000_000

    def tt_metrics_port(self):
        if self._httpd is None:
            return -1
        return self._httpd.server_address[1]

    def tt_now_ns(self):
        return time.monotonic_ns()

    def tt_dump_timeline(self, path):
        import json

        path = path.decode() if isinstance(path, bytes) else path
        with self._lock:
            events = [
                {
                    "name": n, "ph": "X", "ts": s / 1e3, "dur": d / 1e3,
                    "pid": 0, "tid": k, "cat": "tpu",
                }
                for n, s, d, k in self._events
            ]
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        return 0

    def tt_shutdown(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


class ExecutionTimer:
    """Process-wide timer; spans + steps + hang signal.

    Usage::

        timer = get_timer()
        with timer.span("load_batch"):
            ...
        timer.step_start(); ...; timer.step_end(step)
    """

    KIND_SPAN = 0
    KIND_STEP = 1
    KIND_COLLECTIVE = 2
    KIND_CKPT = 3

    def __init__(self, metrics_port: int = 0, hang_timeout_secs: float = 300,
                 allow_build: bool = False):
        lib = _load_native(allow_build)
        self.native = lib is not None
        self._lib = lib if lib is not None else _PyFallback()
        if lib is not None:
            lib.tt_init.restype = ctypes.c_int
            lib.tt_init.argtypes = [ctypes.c_int, ctypes.c_int64]
            lib.tt_record.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_int,
            ]
            lib.tt_set_gauge.argtypes = [ctypes.c_char_p, ctypes.c_double]
            lib.tt_hang.restype = ctypes.c_int
            lib.tt_seconds_since_activity.restype = ctypes.c_int64
            lib.tt_metrics_port.restype = ctypes.c_int
            lib.tt_now_ns.restype = ctypes.c_uint64
            lib.tt_dump_timeline.restype = ctypes.c_int
            lib.tt_dump_timeline.argtypes = [ctypes.c_char_p]
        self.metrics_port = self._lib.tt_init(
            metrics_port, int(hang_timeout_secs * 1000)
        )
        self._step_t0: Optional[int] = None
        self.last_step = -1  # local watermark, piggybacked by the monitor
        self._last_tick_ns: Optional[int] = None
        self._records = 0
        # in-flight spans: a STUCK collective's span never records (the
        # record happens on exit), so hang diagnosis needs the spans that
        # are currently open — that's the "which collective" answer the
        # reference gets from hooking every NCCL call
        self._inflight: Dict[int, tuple] = {}
        self._inflight_lock = threading.Lock()

    # -- low-level ---------------------------------------------------------

    def now_ns(self) -> int:
        return int(self._lib.tt_now_ns())

    def record(self, name: str, start_ns: int, dur_ns: int,
               kind: int = KIND_SPAN):
        self._records += 1
        self._lib.tt_record(name.encode(), start_ns, dur_ns, kind)

    @property
    def instrumented(self) -> bool:
        """True once any activity was recorded — the hang watchdog is only
        meaningful for processes that actually feed the timer (otherwise a
        healthy-but-uninstrumented worker would look permanently hung)."""
        return self._records > 0

    def kick(self):
        self._lib.tt_kick()

    def set_gauge(self, name: str, value: float):
        self._lib.tt_set_gauge(name.encode(), float(value))

    def hang_detected(self) -> bool:
        return bool(self._lib.tt_hang())

    def seconds_since_activity(self) -> int:
        return int(self._lib.tt_seconds_since_activity())

    def dump_timeline(self, path: str) -> bool:
        return self._lib.tt_dump_timeline(path.encode()) == 0

    # -- spans / steps -----------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, kind: int = KIND_SPAN):
        t0 = self.now_ns()
        tid = threading.get_ident()
        with self._inflight_lock:
            # a STACK per thread: nested spans must not erase the still-
            # open outer span from hang diagnosis
            self._inflight.setdefault(tid, []).append((name, t0, kind))
        try:
            yield
        finally:
            with self._inflight_lock:
                stack = self._inflight.get(tid)
                if stack:
                    stack.pop()
                    if not stack:
                        self._inflight.pop(tid, None)
            self.record(name, t0, self.now_ns() - t0, kind)

    def current_spans(self):
        """Open spans: [(name, elapsed_secs, kind)], longest first."""
        now = self.now_ns()
        with self._inflight_lock:
            items = [s for stack in self._inflight.values() for s in stack]
        spans = [(n, (now - t0) / 1e9, k) for n, t0, k in items]
        # checkpoint boundaries are trace spans, fed to this timer when
        # they close: one that never closes is a hang's diagnosis too
        from dlrover_tpu.observability import trace

        spans += [
            (r["name"], r["open_for_s"], self.KIND_CKPT)
            for r in trace.open_spans() if r["name"].startswith("flash.")
        ]
        spans.sort(key=lambda s: -s[1])
        return spans

    def stuck_span(self):
        """(name, elapsed_secs) of the longest open span, or None."""
        spans = self.current_spans()
        return (spans[0][0], spans[0][1]) if spans else None

    def dump_hang_artifacts(self, out_dir: str) -> Dict[str, str]:
        """On-hang evidence: all-thread stacks + Chrome timeline.

        The reference's xpu_timer manager collects stacks via py-spy/
        pstack on hang (``xpu_timer/xpu_timer/common/manager.cc:394-414``);
        here the process dumps itself — ``faulthandler`` walks every
        thread without needing the GIL cooperation of the stuck one."""
        import faulthandler

        os.makedirs(out_dir, exist_ok=True)
        pid = os.getpid()
        paths: Dict[str, str] = {}
        stack_path = os.path.join(out_dir, f"hang_stacks_{pid}.txt")
        try:
            with open(stack_path, "w") as f:
                stuck = self.stuck_span()
                if stuck:
                    f.write(
                        f"stuck in span {stuck[0]!r} for {stuck[1]:.1f}s\n"
                    )
                f.write(
                    f"{self.seconds_since_activity()}s since last timed "
                    "activity; all-thread stacks follow\n\n"
                )
                f.flush()
                faulthandler.dump_traceback(file=f, all_threads=True)
            paths["stacks"] = stack_path
        except OSError as e:  # pragma: no cover
            logger.warning("stack dump failed: %s", e)
        timeline_path = os.path.join(out_dir, f"hang_timeline_{pid}.json")
        if self.dump_timeline(timeline_path):
            paths["timeline"] = timeline_path
        return paths

    def tick_step(self, step: int = -1):
        """Between-call step timing: in steady state the gap between
        successive train-step dispatches IS the step time (buffer donation
        blocks the next dispatch).  Also maintains the global-step gauge."""
        now = self.now_ns()
        if self._last_tick_ns is not None:
            self.record(
                "train_step", self._last_tick_ns, now - self._last_tick_ns,
                self.KIND_STEP,
            )
        else:
            # the FIRST tick must already instrument+kick: a hang during
            # step 1 or its compile is the most common hang, and an
            # un-instrumented timer is ignored by the monitor
            self.record("train_start", now, 0, self.KIND_STEP)
        self._last_tick_ns = now
        if step >= 0:
            self.last_step = step
            self.set_gauge("XPU_TIMER_GLOBAL_STEP", step)

    def step_start(self):
        self._step_t0 = self.now_ns()

    def step_end(self, step: int = -1):
        if self._step_t0 is None:
            return
        dur = self.now_ns() - self._step_t0
        self.record("train_step", self._step_t0, dur, self.KIND_STEP)
        if step >= 0:
            self.last_step = step
            self.set_gauge("XPU_TIMER_GLOBAL_STEP", step)
        self._step_t0 = None

    def shutdown(self):
        self._lib.tt_shutdown()


_timer: Optional[ExecutionTimer] = None
_timer_lock = threading.Lock()


def get_timer(metrics_port: Optional[int] = None,
              hang_timeout_secs: Optional[float] = None) -> ExecutionTimer:
    """Process singleton; first call fixes the configuration."""
    global _timer
    if _timer is None:
        with _timer_lock:
            if _timer is None:
                _timer = ExecutionTimer(
                    metrics_port=(
                        metrics_port
                        if metrics_port is not None
                        else envs.get_int("DLROVER_TPU_TIMER_PORT")
                    ),
                    hang_timeout_secs=(
                        hang_timeout_secs
                        if hang_timeout_secs is not None
                        else envs.get_float("DLROVER_TPU_TIMER_HANG_SECS")
                    ),
                )
                # checkpoint spans (``flash.*``) feed this timer's
                # per-name aggregates and timeline when they close
                from dlrover_tpu.observability import trace

                trace.attach_timer(_timer)
    return _timer


@contextlib.contextmanager
def span(name: str):
    with get_timer().span(name):
        yield
