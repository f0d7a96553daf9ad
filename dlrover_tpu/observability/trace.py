"""W3C-traceparent-style distributed trace context for the control plane.

One trace = one causal story ("why was step 4812 slow?"): a 128-bit
``trace_id`` minted at the root operation, a 64-bit ``span_id`` per
operation, and ``parent_span_id`` links forming the tree.  The current
span rides a :mod:`contextvars` ContextVar, so instrumentation never
threads ids through call signatures; crossing a process boundary means
serializing ``traceparent()`` into the RPC envelope (``Message
.trace_ctx``, the unified-RPC request dict) and opening a server span
from it on the other side.

Design constraints:

1. **Never break the control plane.**  Exporting a span goes through
   the training-event exporter machinery, which already guarantees
   instrumentation failures stay out of training; everything else here
   is a contextvar read and a couple of dict writes.
2. **Seeded-RNG discipline.**  Ids come from one module ``Random``;
   ``DLROVER_TPU_TRACE_SEED`` (or :func:`seed_ids`) makes the id stream
   deterministic for drills and golden-output tests — the same
   discipline the chaos engine uses.  Seeded mode is meant for
   single-process drills; multi-process jobs keep the entropy default.
3. **Cheap when off, cheap enough when on to stand on the hot path.**
   ``DLROVER_TPU_TRACE=0`` turns :func:`span` into a no-op yielding the
   shared :data:`NOOP_SPAN`; the flag is read once per process
   (:func:`seed_ids` and :func:`set_span_sink`, the test hooks, re-read
   it).  A span is one slotted object, integer ``time.time_ns()``
   stamps, lock-free ids, and at close one tuple into the flight
   recorder's ring plus a per-name aggregate.  Spans of the step and
   stager path (:data:`IN_MEMORY_PREFIXES`) stop there: they are never
   serialised one by one, ``flight_recorder.snapshot()`` renders them
   when an incident asks.  Those of them that are not opened every
   step (:data:`PER_STEP_SPANS`) also say how much of their wall time
   their thread spent on a CPU (the attribute ``cpu_ns``, from
   ``time.thread_time_ns()`` at open and close): a long span whose
   ``cpu_ns`` is small waited, one whose ``cpu_ns`` is near its length
   computed.
4. **One clock with the device trace.**  In a process that has already
   imported ``jax`` (this module never imports it) a span also enters a
   ``jax.profiler.TraceAnnotation`` of its name, so while ANY profiler
   session is active the span is in the xplane's ``/host:CPU`` plane on
   the line of its thread.  No session, no trace output: the session is
   the switch, there is no knob.

Span *events* are the attachment point for the PR-4 subsystems: retry
attempts, circuit-breaker flips, and chaos injections call
:func:`add_event` and land on whatever span is live — a seeded chaos
drill therefore yields a fully attributed fault trace.
"""

import contextvars
import dataclasses
import os
import random
import re
import sys
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from dlrover_tpu.common import envs
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability import flight_recorder, goodput

#: span kinds (OpenTelemetry vocabulary, lowercase)
INTERNAL = "internal"
CLIENT = "client"
SERVER = "server"

_TRACEPARENT_VERSION = "00"

# ---------------------------------------------------------------------------
# Id generation: one module RNG, optionally seeded.
# ---------------------------------------------------------------------------

_ids_mu = threading.Lock()
_ids_rng: Optional[random.Random] = None


def seed_ids(seed: int) -> None:
    """Re-seed the id stream (tests/drills).  ``seed=0`` restores the
    entropy default.  Also re-reads ``DLROVER_TPU_TRACE`` and the
    sampling rate, which are otherwise read once per process."""
    global _ids_rng, _ENABLED
    _ENABLED = None
    with _ids_mu:
        _ids_rng = random.Random(seed) if seed else None


def _make_rng() -> random.Random:
    global _ids_rng
    with _ids_mu:
        if _ids_rng is None:
            seed = envs.get_int("DLROVER_TPU_TRACE_SEED")
            _ids_rng = random.Random(
                seed or (
                    int.from_bytes(os.urandom(8), "big")
                    ^ (os.getpid() << 17)
                    ^ time.time_ns()
                )
            )
        return _ids_rng


# ``Random.getrandbits`` is one C call, atomic under the GIL: the id
# stream needs the lock only to be (re)made, not to be drawn from.
def new_trace_id() -> str:
    return "%032x" % (_ids_rng or _make_rng()).getrandbits(128)


def new_span_id() -> str:
    return "%016x" % (_ids_rng or _make_rng()).getrandbits(64)


# ---------------------------------------------------------------------------
# Context + spans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """The wire-portable part of a span: what ``traceparent`` carries."""

    trace_id: str
    span_id: str
    sampled: bool = True

    def traceparent(self) -> str:
        flags = "01" if self.sampled else "00"
        return (
            f"{_TRACEPARENT_VERSION}-{self.trace_id}-{self.span_id}-{flags}"
        )


def parse_traceparent(header: str) -> Optional[TraceContext]:
    """``00-<32 hex>-<16 hex>-<2 hex>`` -> TraceContext, else None.
    Unknown versions are accepted (forward compatibility), malformed
    ids are not."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id, flags = parts[0], parts[1], parts[2], parts[3]
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
        sampled = bool(int(flags, 16) & 1)
    except ValueError:
        return None
    if int(trace_id, 16) == 0 or int(span_id, 16) == 0:
        return None
    return TraceContext(trace_id=trace_id, span_id=span_id, sampled=sampled)


class SpanTuple(NamedTuple):
    """A finished span as the flight recorder's ring holds it."""

    name: str
    start_ns: int
    end_ns: int
    tid: int
    thread: str
    trace_id: str
    span_id: str
    parent_span_id: str
    kind: str
    status: str
    error: str
    attrs: Dict[str, Any]
    events: List[Dict[str, Any]]


_new_tuple = tuple.__new__  # SpanTuple's own __new__ costs a Python call

#: the calling thread's CPU time, where the platform has such a clock
_thread_time_ns = getattr(time, "thread_time_ns", None)


def record_of(t: SpanTuple) -> Dict[str, Any]:
    """The JSONL record the timeline assembler and the incident dump
    consume (``ts``/``dur`` in seconds)."""
    return {
        "ts": round(t.start_ns * 1e-9, 6),
        "dur": round(max(0, t.end_ns - t.start_ns) * 1e-9, 6),
        "name": t.name,
        "type": "SPAN",
        "kind": t.kind,
        "trace_id": t.trace_id,
        "span_id": t.span_id,
        "parent_span_id": t.parent_span_id,
        "status": t.status,
        **({"error": t.error} if t.error else {}),
        "tid": t.tid,
        "thread": t.thread,
        "attrs": t.attrs,
        "events": t.events,
    }


class Span:
    """One traced operation, and its own context manager.  Mutable
    until :meth:`end`; exported once."""

    __slots__ = (
        "name", "kind", "trace_id", "span_id", "parent_span_id",
        "start_ns", "end_ns", "tid", "thread", "attrs", "events",
        "status", "error", "sampled", "_token", "_anno", "_cpu0",
    )

    def __init__(self, name: str, kind: str, trace_id: str, span_id: str,
                 parent_span_id: str = "", sampled: bool = True,
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.kind = kind
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id
        self.tid = threading.get_ident()
        self.thread = threading.current_thread().name
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.events: List[Dict[str, Any]] = []
        self.status = "ok"
        self.error = ""
        self.sampled = sampled
        self._anno = None
        self.end_ns = 0
        self.start_ns = time.time_ns()
        # a hot-path span that is not opened every step reads its
        # thread's CPU clock at both ends, inside its wall stamps
        self._cpu0 = (
            _thread_time_ns()
            if _thread_time_ns is not None
            and name.startswith(IN_MEMORY_PREFIXES)
            and name not in PER_STEP_SPANS else None
        )

    # -- mutation ----------------------------------------------------------

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def set_attrs(self, attrs: Dict[str, Any]) -> None:
        self.attrs.update(attrs)

    def add_event(self, name: str, **attrs: Any) -> None:
        """Attach a timestamped event (retry attempt, breaker flip,
        chaos fault).  Bounded: a retry storm must not grow a span
        without limit."""
        if len(self.events) >= envs.get_int("DLROVER_TPU_TRACE_MAX_EVENTS"):
            return
        self.events.append(
            {"ts": round(time.time(), 6), "name": name, "attrs": attrs}
        )

    def end(self, status: Optional[str] = None, error: str = "") -> None:
        if self.end_ns:
            return
        if self._cpu0 is not None:
            self.attrs["cpu_ns"] = _thread_time_ns() - self._cpu0
        self.end_ns = time.time_ns()
        if status is not None:
            self.status = status
        if error:
            self.error = error

    def context(self) -> TraceContext:
        return TraceContext(
            trace_id=self.trace_id, span_id=self.span_id,
            sampled=self.sampled,
        )

    def traceparent(self) -> str:
        return self.context().traceparent()

    def as_tuple(self) -> SpanTuple:
        return _new_tuple(SpanTuple, (
            self.name, self.start_ns, self.end_ns or time.time_ns(),
            self.tid, self.thread, self.trace_id, self.span_id,
            self.parent_span_id, self.kind, self.status, self.error,
            self.attrs, self.events,
        ))

    # -- the context manager ------------------------------------------------

    def __enter__(self) -> "Span":
        self._token = _CURRENT.set(self)
        # plain dict writes, atomic under the GIL: open_spans() copies
        # the values in one C call
        _OPEN[id(self)] = self
        anno_cls = _ANNOTATION or _find_annotation()
        if anno_cls is not None:
            self._anno = anno_cls(self.name)
            self._anno.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._anno is not None:
            self._anno.__exit__(exc_type, exc, tb)
            self._anno = None
        if exc is not None:
            self.end(status="error", error=f"{exc_type.__name__}: {exc}")
        else:
            self.end()
        _OPEN.pop(id(self), None)
        _CURRENT.reset(self._token)
        _export(self)
        return False


class _NoopSpan:
    """Shared do-nothing span handed out when tracing is disabled (or a
    root is head-sampled away and export suppressed entirely)."""

    name = ""
    kind = INTERNAL
    trace_id = ""
    span_id = ""
    parent_span_id = ""
    sampled = False
    attrs: Dict[str, Any] = {}
    events: List[Dict[str, Any]] = []

    def set_attr(self, key: str, value: Any) -> None:
        pass

    def set_attrs(self, attrs: Dict[str, Any]) -> None:
        pass

    def add_event(self, name: str, **attrs: Any) -> None:
        pass

    def end(self, status: Optional[str] = None, error: str = "") -> None:
        pass

    def context(self) -> None:
        return None

    def traceparent(self) -> str:
        return ""

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NOOP_SPAN = _NoopSpan()

_CURRENT: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "dlrover_tpu_trace_span", default=None
)

# every live (not yet ended) span, across ALL threads: the flight
# recorder's snapshot reads this to name the operation that never
# finished — in a hang, the stuck span IS the diagnosis, and it is by
# definition absent from the finished-span ring
_OPEN: Dict[int, Span] = {}

# -- the bridge to the profiler ----------------------------------------------
#
# ``jax.profiler.TraceAnnotation`` costs an atomic flag check while no
# profiler session is active, and while one is (the benchmark's
# ``--trace 1``, ``timer/device_events.py``'s sampled capture, an
# operator's own) puts the span into the xplane's host plane on the
# device trace's clock.  This module never imports jax: masters and
# agents stay off it, and a worker has imported it long before its
# first span of the step path.
_ANNOTATION: Optional[type] = None


def _find_annotation() -> Optional[type]:
    global _ANNOTATION
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        _ANNOTATION = jax.profiler.TraceAnnotation
    except AttributeError:  # jax is only half imported yet: look again
        return None
    return _ANNOTATION


def open_spans() -> List[Dict[str, Any]]:
    """Records of every currently-open span (any thread), longest-open
    first, with a live ``open_for_s``."""
    now_ns = time.time_ns()
    spans = list(_OPEN.values())
    out = []
    for sp in spans:
        # per-span fault isolation: these spans are LIVE and owned by
        # other threads — a dict(sp.attrs) racing a concurrent set can
        # raise, and one racy span must not void the whole list (the
        # incident dump's stuck-span evidence)
        try:
            attrs = dict(sp.attrs)
        except RuntimeError:
            attrs = {}
        try:
            out.append(
                {
                    "name": sp.name,
                    "kind": sp.kind,
                    "trace_id": sp.trace_id,
                    "span_id": sp.span_id,
                    "parent_span_id": sp.parent_span_id,
                    "start_ts": round(sp.start_ns * 1e-9, 6),
                    "open_for_s": round(
                        max(0, now_ns - sp.start_ns) * 1e-9, 6
                    ),
                    "tid": sp.tid,
                    "thread": sp.thread,
                    "attrs": attrs,
                }
            )
        except Exception:  # noqa: BLE001 - skip the racy span, keep the rest
            continue
    out.sort(key=lambda r: -r["open_for_s"])
    return out


_ENABLED: Optional[bool] = None
_SAMPLE = 1.0


def enabled() -> bool:
    global _ENABLED, _SAMPLE
    if _ENABLED is None:
        _SAMPLE = envs.get_float("DLROVER_TPU_TRACE_SAMPLE")
        _ENABLED = envs.get_bool("DLROVER_TPU_TRACE")
    return _ENABLED


def current_span() -> Optional[Span]:
    return _CURRENT.get()


def current_traceparent() -> str:
    """The header to inject into an outgoing RPC ("" when no live
    span / tracing off)."""
    sp = _CURRENT.get()
    if sp is None or not enabled():
        return ""
    return sp.traceparent()


def add_event(name: str, **attrs: Any) -> bool:
    """Attach an event to the live span, if any.  The hook the retry
    policy, circuit breaker, and chaos engine call — they never hold a
    span themselves."""
    sp = _CURRENT.get()
    if sp is None:
        return False
    sp.add_event(name, **attrs)
    return True


#: trace-time records already made with no span open (a bare
#: ``model.init``): one for each distinct reading, not one a layer
_noted_without_span = set()
#: name -> every distinct reading of the process, oldest first, kept for
#: the run: the recorder's ring rotates the span that carried one out
#: after some hundred steps, and what was chosen at trace time still holds
_trace_time_notes: Dict[str, List[Dict[str, Any]]] = {}


def trace_time_notes(name: str) -> List[Dict[str, Any]]:
    """Every distinct ``note_trace_time(name, ...)`` reading since the
    process began, oldest first (as many as programs were traced, not as
    steps ran)."""
    return [dict(attrs) for attrs in _trace_time_notes.get(name, ())]


def note_trace_time(name: str, **attrs: Any) -> None:
    """One record, and one line in the log, of what Python chose while a
    step was being traced (``attention.path``, ``moe.path``): an event on
    the span open at the time (``trainer.step.dispatch``), a span of its
    own where none is open.  The layers of one trace make the same
    reading; only the first is kept, in the span and in
    ``trace_time_notes``.  It runs while tracing and costs a step
    nothing."""
    kept = _trace_time_notes.setdefault(name, [])
    if attrs not in kept:
        kept.append(dict(attrs))
    open_span = _CURRENT.get()
    if open_span is not None:
        if any(e["name"] == name and e["attrs"] == attrs
               for e in open_span.events):
            return
        open_span.add_event(name, **attrs)
    else:
        key = (name, tuple(attrs.items()))
        if key in _noted_without_span:
            return
        _noted_without_span.add(key)
        with span(name, attrs=attrs):
            pass
    logger.info("%s %s", name, " ".join(f"{k}={v}" for k, v in attrs.items()))


# ---------------------------------------------------------------------------
# Device scopes: which layer of the program asked for each instruction of a
# compiled program.  Flax pushes every module's name onto JAX's name stack,
# the program adds ``jax.named_scope`` where no module stands (the loss, the
# optimizer's pass, the parts of an attention or of the routed block), and
# the compiler carries the stack to each instruction as ``op_name``.  The
# table below is the one place that says what a name means.
# ---------------------------------------------------------------------------

#: a scope's name, as it stands in an ``op_name`` -> the kind of work it is.
#: The innermost scope of a path that is listed here decides.  Module names
#: are parameter names, so the table follows them; what has no module has a
#: ``jax.named_scope`` of the kind's own name.
SCOPE_KINDS: Dict[str, str] = {
    "embed": "embed",
    "input_norm": "norm", "post_attn_norm": "norm", "final_norm": "norm",
    # the sandwich norms on a branch's output (``sandwich_norm``)
    "attn_out_norm": "norm", "mlp_out_norm": "norm",
    "ln_1": "norm", "ln_2": "norm", "ln_f": "norm",
    "attn": "attn.proj",
    "attn.core": "attn.core",
    "mlp": "mlp",
    "moe": "moe",
    "lm_head": "head_loss", "head_loss": "head_loss",
    "optimizer": "optimizer",
    "grad_sync": "grad_sync",
}

#: the parts of a kind that have a scope of their own inside it (no name
#: twice: a sub-scope says whose it is)
SUB_SCOPES: Dict[str, Tuple[str, ...]] = {
    "attn.core": ("scores", "select", "selected", "index_loss",
                  "pool", "windows", "summary_mass",
                  # a gated delta-rule layer (``models/llama.py::
                  # DeltaAttention``, ``ops/linear_attention.py``)
                  "conv", "decay", "chunk", "state", "gate",
                  # attention under the block-diffusion mask (``ops/
                  # attention.py::block_diffusion_attention``).  On a TPU
                  # ONE kernel call a pass for both halves, under
                  # ``bd_noisy``; ``bd_keys`` (the joining of a noisy
                  # block's keys and the mask from ``iota``) and
                  # ``bd_clean`` hold the ``jax.numpy`` path's work alone
                  "bd_keys", "bd_clean", "bd_noisy",
                  # the core of latent attention (``ops/attention.py::
                  # latent_attention``)
                  "latent",
                  # a softmax layer under a causal window (``models/
                  # llama.py::Attention`` of kind ``swa``): the FA2 kernels
                  # with ``window``, or the reference core under the band
                  "window",
                  # a state-space layer (``models/llama.py::MambaMixer``:
                  # ``conv``, ``decay`` and ``gate`` as above, and the
                  # selective scan, ``ops/selective_scan.py``), the core of
                  # differential attention (``Attention._differential``;
                  # under ``window`` where there is one: the innermost
                  # names the work), a gated memory unit's gate, and what the
                  # memory layers of a decoder-hybrid-decoder stack hand to
                  # its cross-decoder (``_MemoryLayers``, the module
                  # ``memory``: the cast of ``Y``; backward the memory's
                  # gradient summed over its readers)
                  "scan", "diff", "gmu", "handed",
                  # a Mamba-2 layer (``models/llama.py::Mamba2Mixer``:
                  # ``conv``, ``decay`` and ``gate`` as above, the gate with
                  # its group norm): the chunked scan of ``ops/ssd.py``, the
                  # scores, the decay mask, the two products and the state's
                  # carry between chunks
                  "ssd",
                  # a double-gated short convolution (``models/llama.py::
                  # ShortConvMixer``, ``ops/short_conv.py``): the two gates
                  # and the taps between them, all passes.  NOT ``conv``,
                  # which is the SiLU'd taps in front of a scan's or the
                  # delta rule's core: a sub-scope says whose work it is
                  "gconv"),
    # what latent attention adds around its core (``models/llama.py::
    # LatentAttention``): the latent's projections, its norm, the rotary
    # part.  The one name two kinds have: a kind each, and a path that
    # starts anew at it is the core's (``_SUB_SCOPE_OF``: kernels)
    "attn.proj": ("latent",),
    # the sampling of block diffusion's noise (``models/llama.py``)
    "embed": ("noise",),
    # ``latent``: the two projections around experts that work in a latent
    # (``models/moe.py``, ``moe_latent_size``); the name is ``attn.core``'s
    # where a path starts anew at it
    "moe": ("route", "sort", "gmm", "exchange", "combine", "shared",
            "latent"),
    # a router's selection bias moved by the load (``models/moe.py``)
    "optimizer": ("bias",),
    # a looped stack's exits (``models/llama.py::_looped_stack``): the gate
    # after every loop step, the exit distribution, the weighting of the
    # exits' losses and the entropy; the heads and their cross entropies
    # stand under ``head_loss`` itself
    "head_loss": ("exit",),
}

_SUB_SCOPE_OF = {sub: kind for kind, subs in reversed(SUB_SCOPES.items())
                 for sub in subs}

#: names the compiler gives an instruction in place of a path: the grouped
#: matmul ``jax.lax.ragged_dot`` becomes, which only the routed block asks for
COMPILER_NAMES: Dict[str, Tuple[str, str]] = {
    "ragged-dot-none": ("moe", "gmm"),
    "ragged-dot-metadata": ("moe", "gmm"),
}

OTHER, UNNAMED = "other", "unnamed"
FORWARD, REMAT, BACKWARD = "forward", "remat", "backward"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%?[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%[\w.\-]+")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def unwrapped(part: str) -> str:
    """One component of an ``op_name`` without the transformations around
    it: a transformation wraps the scope it was applied under
    (``jvp(head_loss)``, ``transpose(jvp(LlamaForCausalLM))``).  What
    ``jit(..)`` wraps is a function's name, not a scope: left as it is."""
    wrapped = _WRAPPED.match(part)
    while wrapped and wrapped.group(1) not in ("jit", "pjit"):
        part = wrapped.group(2)
        wrapped = _WRAPPED.match(part)
    return part


def scope_of(op_name: str) -> Tuple[str, str, str]:
    """``(kind, sub-scope, pass)`` of one instruction from its ``op_name``.

    The path is split at ``/``; what a transformation adds (``jit(..)``,
    ``while``, ``body``, ``closed_call``, ``checkpoint``, ...) names no
    layer and is skipped simply by not being in :data:`SCOPE_KINDS``;
    ``jvp(..)``, ``transpose(..)``, ``vmap(..)`` are taken off the scope
    they wrap; the last component is the primitive.  ``pass``:
    ``remat`` under ``rematted_computation`` (the forward pass computed
    again inside the backward), else ``backward`` under ``transpose(``, else
    ``forward``.  A path none of whose scopes has a kind is ``other``; no
    path at all (no ``op_name``, a bare name the compiler made up, or an
    argument's name on the copy that changes its layout) is ``unnamed``,
    with no pass."""
    # a fusion of several operations may list their paths, ``;`` between:
    # the first is its root's
    op_name = op_name.partition(";")[0]
    if op_name in COMPILER_NAMES:
        return COMPILER_NAMES[op_name] + (FORWARD,)
    if "/" not in op_name:
        return UNNAMED, "", ""
    if "rematted_computation" in op_name:
        which = REMAT
    elif "transpose(" in op_name:
        which = BACKWARD
    else:
        which = FORWARD
    kind, sub = OTHER, ""
    for part in op_name.split("/")[:-1]:
        part = unwrapped(part)
        if part in SCOPE_KINDS:
            kind, sub = SCOPE_KINDS[part], ""
        elif part in SUB_SCOPES.get(kind, ()):
            sub = part
        elif kind == OTHER and part in _SUB_SCOPE_OF:
            # the path starts anew below its kind's scope (the body of a
            # reduction keeps only the innermost names)
            kind, sub = _SUB_SCOPE_OF[part], part
    return kind, sub, which


class DeviceScopes(NamedTuple):
    """What :func:`device_scopes` returns; instruction names as the device
    trace has them (``%fusion.281``)."""

    #: instruction -> (kind, sub-scope, pass)
    scopes: Dict[str, Tuple[str, str, str]]
    #: instruction -> its first operand's name
    first_operand: Dict[str, str]
    #: instruction -> the instructions that take it as an operand
    users: Dict[str, Tuple[str, ...]]


def parse_device_scopes(hlo_text: str) -> DeviceScopes:
    """One pass over a compiled program's text (``compiled.as_text()``):
    every instruction's scope from its ``metadata={op_name=...}``, with its
    first operand and its users, so that an instruction the compiler left
    without a path (a layout ``copy``) can be given its neighbour's."""
    scopes, first_operand, users = {}, {}, {}
    for line in hlo_text.split("\n"):
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name = found.group(1)
        name = name if name.startswith("%") else "%" + name
        body = line[found.end():]
        # operands stand between the opcode's bracket and the attributes;
        # a name inside the attributes (``calls=%fused_computation``) comes
        # after every operand, so the first name is the first operand
        operands = _OPERAND.findall(body.partition("), ")[0])
        if operands:
            first_operand[name] = operands[0]
            for operand in dict.fromkeys(operands):
                users.setdefault(operand, []).append(name)
        op_name = _OP_NAME.search(body)
        scopes[name] = scope_of(op_name.group(1) if op_name else "")
    return DeviceScopes(
        scopes, first_operand, {k: tuple(v) for k, v in users.items()})


#: name -> a function that returns the compiled program's text, left by
#: whoever compiled it (``Trainer``: ``trainer.step``), and what it gave
_scope_thunks: Dict[str, Callable[[], str]] = {}
_scope_maps: Dict[str, DeviceScopes] = {}


def register_device_scopes(name: str, compiled_text: Callable[[], str]) -> None:
    """Leave the way to a compiled program's text under ``name``.  Nothing
    runs here; a later registration under the name replaces the earlier."""
    _scope_thunks[name] = compiled_text
    _scope_maps.pop(name, None)


def device_scopes(name: str) -> Optional[DeviceScopes]:
    """The scope of every instruction of the program registered as ``name``
    (``None`` where none is).  Evaluated on request only, once: the thunk
    fetches the program's text (``Trainer``'s asks JAX for the executable it
    already holds: nothing is lowered or compiled again) and one pass reads
    it, a fraction of a second that belongs outside any measured step.  One
    ``<name>.scopes`` record says what it found and what it cost."""
    if name in _scope_maps:
        return _scope_maps[name]
    thunk = _scope_thunks.get(name)
    if thunk is None:
        return None
    t0 = time.time()
    text = thunk()
    t_text = time.time()
    found = _scope_maps[name] = parse_device_scopes(text)
    kinds = sorted({kind for kind, _, _ in found.scopes.values()})
    note_trace_time(
        name + ".scopes", instructions=len(found.scopes),
        named=sum(kind != UNNAMED for kind, _, _ in found.scopes.values()),
        kinds=",".join(kinds), text_s=round(t_text - t0, 3),
        parse_s=round(time.time() - t_text, 3))
    return found


def span(name: str, kind: str = INTERNAL,
         attrs: Optional[Dict[str, Any]] = None,
         parent: Optional[TraceContext] = None):
    """A span to open with ``with`` as the new current context.

    Parentage: an explicit ``parent`` (a remote TraceContext, or the
    context a queue item carried from another thread) wins; else the
    live span; else this is a root (new trace id, head sampling
    applies).  An exception ends the span with ``status="error"`` and
    re-raises.
    """
    if not (_ENABLED if _ENABLED is not None else enabled()):
        return NOOP_SPAN
    if parent is None:
        parent = _CURRENT.get()
    rng = _ids_rng or _make_rng()
    if parent is not None:
        return Span(
            name, kind, parent.trace_id, "%016x" % rng.getrandbits(64),
            parent.span_id, parent.sampled, attrs,
        )
    return Span(
        name, kind, "%032x" % rng.getrandbits(128),
        "%016x" % rng.getrandbits(64), "",
        _SAMPLE >= 1.0 or rng.random() < _SAMPLE, attrs,
    )


def server_span(name: str, traceparent: str,
                attrs: Optional[Dict[str, Any]] = None):
    """Open the server side of an RPC: parented to the remote caller's
    span when ``traceparent`` parses, a fresh root otherwise."""
    return span(
        name, kind=SERVER, attrs=attrs, parent=parse_traceparent(traceparent)
    )


# ---------------------------------------------------------------------------
# Where a finished span goes.  Every span: the flight recorder's ring (a
# tuple) and per-name aggregate, and the goodput ledger where its name
# maps to a phase.  Control-plane spans also become SPAN records in the
# per-process event stream (or a dedicated DLROVER_TPU_TRACE_FILE),
# which the timeline assembler later joins across processes; spans of
# the step and stager path do not — a JSON line a span is not a cost the
# hot path pays.
# ---------------------------------------------------------------------------

#: names kept in memory only (docs/observability.md, span taxonomy)
IN_MEMORY_PREFIXES: Tuple[str, ...] = (
    "trainer.", "flash.save", "flash.stage",
)

#: the three of them a step opens: they take no CPU clock of their own.
#: The thread's CPU clock is a system call, 0.3 us on a plain kernel and 7 us
#: with a tick of 10 ms on the sandboxed one the benchmark's machines run
#: (PERF.md, PR 53): six reads a step cost 45 us there and read 0 on spans of
#: a millisecond.  The step's account covers them at one read a step
#: (``interval_cpu_ns``, ``trainer/step_account.py``).
PER_STEP_SPANS = frozenset(
    {"trainer.step", "trainer.step.dispatch", "trainer.shard_batch"})

_sink_mu = threading.Lock()
_sink: Optional[Callable[[Dict[str, Any]], None]] = None

#: the process's ExecutionTimer once one exists (``timer.get_timer``
#: attaches it): checkpoint spans feed its per-name aggregates and
#: timeline at close, on its own clock
_TIMER = None


def attach_timer(timer) -> None:
    global _TIMER
    _TIMER = timer


def set_span_sink(sink: Optional[Callable[[Dict[str, Any]], None]]) -> None:
    """Override where span records go (tests, the CI smoke).  ``None``
    restores the default (the training-event exporter / trace file).
    Also re-reads ``DLROVER_TPU_TRACE``."""
    global _sink, _ENABLED
    _ENABLED = None
    with _sink_mu:
        _sink = sink


def _default_sink() -> Callable[[Dict[str, Any]], None]:
    path = envs.get_str("DLROVER_TPU_TRACE_FILE")
    if path:
        from dlrover_tpu.training_event.emitter import TextFileExporter

        exporter = TextFileExporter(path)
        target = envs.get_str("DLROVER_TPU_ROLE", default="proc")
        pid = os.getpid()

        def _file_sink(record: Dict[str, Any]) -> None:
            exporter.export({"target": target, "pid": pid, **record})

        return _file_sink
    from dlrover_tpu.training_event.emitter import get_default_emitter

    return get_default_emitter().emit_span


def _export(sp: Span) -> None:
    if not sp.sampled:
        return
    t = sp.as_tuple()
    name = t.name
    try:
        # flight recorder first: the ring must hold the span even when
        # the export sink is broken/replaced (tests) — the incident
        # dump is the consumer that must never miss evidence
        flight_recorder.on_span(t)
    except Exception:  # noqa: BLE001 - never break the RPC
        pass
    try:
        # goodput ledger: ckpt/rendezvous spans are wall-clock phases
        claim = goodput.span_phase(name)
        if claim and t.end_ns > t.start_ns:
            goodput.charge_interval(
                claim, t.start_ns * 1e-9, t.end_ns * 1e-9
            )
    except Exception:  # noqa: BLE001 - never break the RPC
        pass
    timer = _TIMER
    if timer is not None and name.startswith("flash."):
        try:
            dur = t.end_ns - t.start_ns
            timer.record(name, timer.now_ns() - dur, dur, timer.KIND_CKPT)
        except Exception:  # noqa: BLE001 - never break a save
            pass
    if name.startswith(IN_MEMORY_PREFIXES):
        return
    global _sink
    with _sink_mu:
        sink = _sink
        if sink is None:
            try:
                sink = _sink = _default_sink()
            except Exception as e:  # noqa: BLE001 - never break the RPC
                logger.debug("span sink unavailable: %s", e)
                return
    try:
        sink(record_of(t))
    except Exception as e:  # noqa: BLE001 - never break the RPC
        logger.debug("span export failed: %s", e)
