"""The step's device time by the program's own scopes, for the seven
``*_scope_*`` readers under ``layer_metrics/``.

The trace names a device operation by its HLO text; the text's first word is
the instruction's name (``%fusion.281``).  The program knows which of its
scopes asked for each instruction of its compiled step
(``dlrover_tpu.observability.trace.device_scopes("trainer.step")``: kind,
sub-scope and pass from the instruction's ``op_name``, the table in that
module).  The stepping job runs in this process, so the reader asks the
program and joins the two by name: no shape is matched here.  A program
without the function (an older commit) leaves every reader with ``None``.

**Self time.**  ``%while``, ``%conditional`` and ``%call`` are open while
their bodies run: an instant belongs to the innermost operation open at it,
so a container keeps what no child covers and nothing is counted twice.  The
self times of all operations sum to the union of their intervals, the
device's busy time.

**Whole steps** (``eva_attn_ms_per_step.whole_steps``' rule): from the first
start of the outermost loop, the ``%while`` with the fewest runs (at least
two), to its last; where a step has no loop, of the instruction with the
fewest runs.  Every operation of a step is once in each period.

**Inheritance.**  The compiler leaves some instructions without a path
(layout copies, what it splits off a fusion).  Such an ``unnamed``
instruction takes, one hop, the scope of its single user, else of the
producer of its first operand; what is still unnamed stays so, and
``scope_unnamed_pct`` counts it.

Arithmetic on plain lists and dictionaries; only ``table_of`` touches what
the job observed."""

import json
import sys

from benchmarks import trace as trace_mod

#: a per-layer metric -> the kinds of the program's table it sums
METRIC_KINDS = {
    "attn_core_scope_ms_per_step": ("attn.core",),
    "attn_proj_scope_ms_per_step": ("attn.proj",),
    "ffn_scope_ms_per_step": ("mlp", "moe"),
    "head_loss_scope_ms_per_step": ("head_loss",),
    "optimizer_scope_ms_per_step": ("optimizer", "grad_sync"),
    "rest_scope_ms_per_step": ("embed", "norm", "other"),
}
UNNAMED = "unnamed"
PROGRAM = "trainer.step"


def self_times(ops):
    """``[(name, seconds)]``, one entry an operation of ``ops`` (``(name,
    start, end)``): the part of its interval in which it is the innermost
    operation open.  An operation that starts inside another and ends after
    it keeps what lies beyond the other's end."""
    out = []
    stack = []    # [name, end, self seconds, covered up to]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, own, at = stack.pop()
            out.append((name, own + max(0.0, end - at)))
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    for name, start, end in sorted(ops, key=lambda op: (op[1], -op[2])):
        close(start)
        if stack:
            top = stack[-1]
            top[2] += max(0.0, start - top[3])
            top[3] = max(top[3], start)
        stack.append([name, end, 0.0, start])
    close(float("inf"))
    return out


def whole_steps(ops):
    """``(start, end, steps)`` of the whole steps ``ops`` hold, or ``None``
    where nothing runs twice."""
    starts, loops = {}, set()
    for text, start, _ in ops:
        name = text.partition(" = ")[0]
        starts.setdefault(name, []).append(start)
        if name.startswith("%while"):
            loops.add(name)
    runs = [sorted(s) for name, s in starts.items()
            if len(s) >= 2 and (not loops or name in loops)]
    if not runs:
        return None
    outermost = min(runs, key=lambda s: (len(s), s[0]))
    return outermost[0], outermost[-1], len(outermost) - 1


def resolve(scopes, first_operand, users):
    """``{instruction: (kind, sub-scope, pass, inherited)}``: ``scopes`` with
    every ``unnamed`` instruction given, one hop, its single user's scope,
    else its first operand's producer's."""
    out = {}
    for name, scope in scopes.items():
        inherited = False
        if scope[0] == UNNAMED:
            mine = users.get(name, ())
            near = [mine[0]] if len(mine) == 1 else []
            near.append(first_operand.get(name))
            for other in near:
                if other in scopes and scopes[other][0] != UNNAMED:
                    scope, inherited = scopes[other], True
                    break
        out[name] = tuple(scope) + (inherited,)
    return out


def cover(ops, resolved):
    """The table: ``{"steps", "period_ms", "busy_ms", "union_ms", "rows":
    {(kind, sub, pass): [ms a step, calls a step, ms a step inherited]},
    "unnamed_ms", "unnamed_before_ms", "unmatched"}`` over the whole steps
    ``ops`` hold, or ``None`` where they hold none.  An operation the
    program's map does not know is ``unnamed`` (``unmatched`` counts them)."""
    steps = whole_steps(ops)
    if not steps:
        return None
    lo, hi, n = steps
    inside = [(text.partition(" = ")[0], max(start, lo), min(end, hi))
              for text, start, end in ops if min(end, hi) > max(start, lo)]
    rows, unmatched, before = {}, set(), 0.0
    for name, seconds in self_times(inside):
        kind, sub, which, inherited = resolved.get(
            name, (UNNAMED, "", "", False))
        if name not in resolved:
            unmatched.add(name)
        row = rows.setdefault((kind, sub, which), [0.0, 0.0, 0.0])
        row[0] += 1e3 * seconds / n
        row[1] += 1.0 / n
        if inherited or kind == UNNAMED:
            before += 1e3 * seconds / n
        if inherited:
            row[2] += 1e3 * seconds / n
    return {
        "steps": n, "period_ms": 1e3 * (hi - lo) / n,
        "busy_ms": sum(row[0] for row in rows.values()), "rows": rows,
        # the check: the union of the same intervals, made another way
        "union_ms": 1e3 * trace_mod.total(trace_mod.union(
            [(start, end) for _, start, end in inside])) / n,
        "unnamed_ms": sum(row[0] for key, row in rows.items()
                          if key[0] == UNNAMED),
        "unnamed_before_ms": before, "unmatched": len(unmatched),
    }


def by_kind(table):
    out = {}
    for (kind, _, _), row in table["rows"].items():
        out[kind] = out.get(kind, 0.0) + row[0]
    return out


def table_of(observed):
    """``cover`` of the first chip's operations inside the traced window
    against the program's map; ``None`` where the trace holds no device
    operation or the program has no map.  The first reader that asks pays
    (the program fetches its compiled step's text once) and leaves the
    table in ``observed`` for the others; it goes to standard error then,
    with the check that the parts sum to the busy time."""
    loaded = observed.get("trace_loaded")
    if loaded is None or not loaded.device_ops:
        return None
    if "device_scopes" in observed:     # an earlier reader of this run
        return observed["device_scopes"]
    try:
        from dlrover_tpu.observability import trace as program_trace

        found = program_trace.device_scopes(PROGRAM)
    except (ImportError, AttributeError):    # a program without the map
        found = None
    table = None
    if found is not None:
        lo, hi = trace_mod.window_of(loaded)
        ops = [op for op in loaded.device_ops[min(loaded.device_ops)]
               if op[1] >= lo and op[2] <= hi]
        table = cover(ops, resolve(*found))
        if table:
            report(table)
    observed["device_scopes"] = table
    return table


def report(table):
    rows = sorted(table["rows"].items(), key=lambda kv: -kv[1][0])
    print(json.dumps({
        "phase": "device_scopes", "steps": table["steps"],
        "period_ms": table["period_ms"], "busy_ms": table["busy_ms"],
        "union_ms": table["union_ms"],
        "by_kind_ms": by_kind(table),
        "unnamed_pct": 100.0 * table["unnamed_ms"] / table["busy_ms"],
        "unnamed_before_inheritance_pct":
            100.0 * table["unnamed_before_ms"] / table["busy_ms"],
        "unmatched_instructions": table["unmatched"],
        "rows": [{"kind": kind, "sub": sub, "pass": which,
                  "ms_per_step": row[0], "calls_per_step": row[1],
                  "inherited_ms": row[2]}
                 for (kind, sub, which), row in rows],
    }), file=sys.stderr, flush=True)


def ms_per_step(observed, metric):
    """What ``metric`` reads: the self time a step of its kinds."""
    table = table_of(observed)
    if not table:
        return None
    kinds = by_kind(table)
    return sum(kinds.get(kind, 0.0) for kind in METRIC_KINDS[metric])


def unnamed_pct(observed):
    """Self time still ``unnamed`` after inheritance over the busy time."""
    table = table_of(observed)
    if not table or not table["busy_ms"]:
        return None
    return 100.0 * table["unnamed_ms"] / table["busy_ms"]
