"""Whether the latent layers' rotary part turned NEIGHBOURING columns, from
the program's own trace-time record: ``attention.path`` of a latent layer
(``impl=latent``) carries ``rope=pairs`` (``(x[2i], x[2i+1])``: DeepSeek-V3's
checkpoint layout, Kanana-2's ``rope_interleave``) or ``rope=halves``
(``(x[i], x[i + 32])``: Ling-3.0's).  1 for pairs, 0 for halves: the run's
own word that the published convention is what was timed.  Read from the
readings the program keeps for the run (``trace.trace_time_notes``: the
step is traced once, during set-up, and the recorder's ring of spans
rotates); from the ring where the program keeps none (an event on the span
open while the step was traced, or a span of its own).  Nothing to read
where no latent layer was traced or the record says nothing of its rotary
part (an older commit)."""

from benchmarks import program_spans

WORDS = {"pairs": 1.0, "halves": 0.0}
NAME = "attention.path"


def records():
    """The attributes of every latent layer's ``attention.path`` record,
    oldest first."""
    try:
        from dlrover_tpu.observability.trace import trace_time_notes

        out = trace_time_notes(NAME)
    except Exception:  # noqa: BLE001 - a program that keeps no such record
        out = []
        for span in program_spans.ring():
            if span.name == NAME:
                out.append(span.attrs)
            out.extend(event.get("attrs", {}) for event in span.events or ()
                       if event.get("name") == NAME)
    return [attrs for attrs in out if attrs.get("impl") == "latent"]


def read(observed):
    found = records()
    return WORDS.get(found[-1].get("rope")) if found else None
