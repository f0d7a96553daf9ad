"""What every job of the benchmark shares, and nothing that touches JAX at
import: the run's record, its scratch directories, the environment a
process of the program needs so that it writes nothing outside the
checkout and ``TMPDIR``, the native libraries, the device record."""

import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
UNIX_PATH_MAX = 107
SOCKET_NAME_ROOM = 48


def load_module(kind, name):
    """``benchmarks/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CheckFailed(Exception):
    def __init__(self, message, **detail):
        super().__init__(message)
        self.detail = detail


@dataclasses.dataclass
class Run:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_process_start: float
    phase: str = "start"
    scratch: str = ""
    sockets: str = ""

    def __post_init__(self):
        # both under TMPDIR; the sockets beside the scratch directory and
        # not inside it, because unix socket paths are short
        self.scratch = tempfile.mkdtemp(prefix="dlrbench_")
        self.sockets = tempfile.mkdtemp(prefix="s")
        if len(self.sockets) + 1 + SOCKET_NAME_ROOM > UNIX_PATH_MAX:
            raise CheckFailed(
                "TMPDIR leaves no room for a unix socket path",
                socket_dir=self.sockets,
            )

    @property
    def chips(self):
        return int(self.cell["chips"])

    @property
    def events_file(self):
        return os.path.join(self.scratch, "events.jsonl")

    def emit(self, record):
        self.phase = record.get("phase", self.phase)
        text = json.dumps(record, default=str)
        print(("REHEARSAL " if self.rehearse else "") + text, flush=True)

    def begin(self, phase):
        self.phase = phase
        return time.time()

    def cleanup(self):
        shutil.rmtree(self.scratch, ignore_errors=True)
        shutil.rmtree(self.sockets, ignore_errors=True)

    def program_env(self, base=None):
        """The environment of a process that runs the program: runtime
        state in directories this run removes, the chip's own logs off,
        and on the CPU (rehearsal only) the platform named."""
        env = dict(os.environ if base is None else base)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("DLROVER_TPU_MASTER_ADDR", None)
        env.pop("BENCH_RUN", None)
        env.update({
            "DLROVER_TPU_SOCKET_DIR": self.sockets,
            "DLROVER_TPU_EVENT_FILE": self.events_file,
            "DLROVER_TPU_INCIDENT_DIR": os.path.join(self.scratch, "incidents"),
            "DLROVER_TPU_LOG_DIR": os.path.join(self.scratch, "hang"),
            "DLROVER_TPU_JOB_STATE_DIR": os.path.join(self.scratch, "jobs"),
            "DLROVER_TPU_PARAL_CONFIG_PATH": os.path.join(
                self.scratch, "paral.json"),
            "DLROVER_TPU_RUNTIME_METRICS_PATH": os.path.join(
                self.scratch, "runtime_metrics.json"),
            "TPU_LOG_DIR": "disabled",
            # every program of a run goes to the persistent cache, the
            # harness's small ones too: set-up then repeats from the cache
            "DLROVER_TPU_COMPILE_CACHE_MIN_S": "0",
            # the comm observatory's probe compiles its own programs at
            # step 200, which would be inside a window (PERF.md, Open
            # questions): off in every cell
            "DLROVER_TPU_COMM_PROBE_EVERY": "0",
        })
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["DLROVER_TPU_PLATFORM"] = "cpu"
            if self.chips > 1:
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={self.chips}"
                ).strip()
            # the tiny state is below the engine's asynchronous floor, and
            # a CPU backend caches only where a directory is named: a
            # throw-away one, CPU entries have no place in the real cache
            env["DLROVER_TPU_ASYNC_MIN_BYTES"] = "0"
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
            env["DLROVER_TPU_COMPILE_CACHE"] = os.path.join(
                self.scratch, "xla")
        return env


def build_native():
    """``libtpu_timer.so`` and ``libfastcopy.so`` built from ``native/``
    into ``native/build/`` (git-ignored, inside the checkout) unless they
    are there already; returns their paths.  Without them the program's
    Python fallbacks run, in silence, and the copy path is another."""
    src = os.path.join(ROOT, "native")
    build = os.path.join(src, "build")
    libs = {lib: os.path.join(build, f"lib{lib}.so")
            for lib in ("tpu_timer", "fastcopy")}
    if all(os.path.exists(p) for p in libs.values()):
        return "found", libs
    shutil.rmtree(build, ignore_errors=True)
    how = "cmake"
    try:
        subprocess.run(["cmake", "-S", src, "-B", build], check=True,
                       capture_output=True, timeout=300)
        subprocess.run(["cmake", "--build", build], check=True,
                       capture_output=True, timeout=600)
    except (OSError, subprocess.SubprocessError) as e:
        how = f"direct (cmake: {type(e).__name__})"
        shutil.rmtree(build, ignore_errors=True)
        os.makedirs(build)
        cxx = next((c for c in ("c++", "g++", "clang++")
                    if shutil.which(c)), None)
        if cxx is None:
            raise CheckFailed("no cmake and no C++ compiler")
        for lib, unit in (("tpu_timer", "tpu_timer/tpu_timer.cc"),
                          ("fastcopy", "fastcopy/fastcopy.cc")):
            proc = subprocess.run(
                [cxx, "-std=c++17", "-O2", "-shared", "-fPIC",
                 os.path.join(src, unit), "-o", libs[lib], "-lpthread"],
                capture_output=True, text=True, timeout=600,
            )
            if proc.returncode != 0:
                raise CheckFailed(f"native build of {lib} failed",
                                  stderr=proc.stderr[-1500:])
    return how, libs


def native_in_use(libs):
    """Which of the built libraries this process really mapped."""
    mapped = {}
    with open("/proc/self/maps") as f:
        for line in f:
            for lib, path in libs.items():
                if line.rstrip().endswith(os.path.basename(path)):
                    mapped[lib] = line.split()[-1]
    return mapped


def device_record(jax):
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_chips(run, device):
    if run.rehearse:
        return
    if device["platform"] != "tpu" or device["count"] != run.chips:
        raise NoChip(f"the cell asks for {run.chips} tpu chip(s); JAX "
                     f"reports {device}")


def memory_peak_bytes(jax):
    """Peak on the fullest chip, or ``None`` where the backend does not
    say (the CPU)."""
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}: "
                       "add it to benchmarks/peaks.json with its source")
    return table[device_kind]


def quantile(values, q):
    """Linear interpolation between closest ranks, ``q`` in [0, 1]."""
    ordered = sorted(values)
    if not ordered:
        return None
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def read_events(path):
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return out


def tail(path, limit=3000):
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - limit))
            return f.read().decode("utf-8", errors="replace")
    except OSError as e:
        return f"<no log: {e}>"


class CompileWatch:
    """The benchmark's own count of compilation, by thread: ``jax.monitoring``
    reports every trace, lowering and backend compile in the thread that
    makes it.  The stepping thread may make none inside a window (every
    shape it uses was warmed up); what other threads of the program compile
    there (the checkpoint stager makes a new slice program for each chunk)
    is reported, not hidden."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import threading

        self._ident = threading.get_ident
        self._stepping = threading.get_ident()
        self._lock = threading.Lock()
        self._totals = {"stepping": [0, 0.0], "other": [0, 0.0]}
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_kw):
        if event in self.EVENTS:
            who = "stepping" if self._ident() == self._stepping else "other"
            with self._lock:
                self._totals[who][0] += 1
                self._totals[who][1] += duration

    def snapshot(self):
        with self._lock:
            return {k: tuple(v) for k, v in self._totals.items()}

    @staticmethod
    def between(before, after):
        return {k: {"events": after[k][0] - before[k][0],
                    "seconds": after[k][1] - before[k][1]} for k in after}
