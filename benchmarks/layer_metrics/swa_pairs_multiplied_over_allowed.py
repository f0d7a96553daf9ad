"""The query-key pairs a head's forward pass of a windowed FA2 call
multiplies over the pairs its band allows, from the program's own
trace-time record: ``attention.path`` of a windowed call carries
``pairs_multiplied`` (every score of every block a query block visits) and
``pairs_allowed`` (``ops/attention.py::flash_attention``).  1 is no masked
score computed; blocks of 512 x 512 under a window of 512 read 2.0, and a
kernel that walked every causal block would read 16.3 at 16,384 positions.
The record is an event on the span open while the step was traced
(``trainer.step.dispatch``), or a span of its own; nothing to read where
the program made none (an older commit, another family, the reference
core)."""

from benchmarks import program_spans


def records():
    """The attributes of every ``attention.path`` record that names a
    window, oldest first."""
    out = []
    for span in program_spans.ring():
        if span.name == "attention.path":
            out.append(span.attrs)
        out.extend(event.get("attrs", {}) for event in span.events or ()
                   if event.get("name") == "attention.path")
    return [attrs for attrs in out if attrs.get("pairs_allowed")]


def read(observed):
    found = records()
    if not found:
        return None
    last = found[-1]
    return last["pairs_multiplied"] / last["pairs_allowed"]
