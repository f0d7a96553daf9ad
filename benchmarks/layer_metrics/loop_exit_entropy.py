"""The mean entropy of a looped stack's exit distribution in nats, from the
program's own counter (``loop_exit_entropy``, sown by the model's forward
pass): the LAST ``trainer.model_stats`` record the window's steps left in
the recorder or, where the window is too short to hold one (the trainer
reads its model's counters every twentieth step, a step behind: at 3 s a
step a window of 51 s ends before the second reading), what the forward
check of the same run sowed on the state the timed path starts from
(``families/ouro.py::SEEN``; the window's steps run at a rate near 0, so
the gate stays where that state put it).  A GAUGE: ln 4 = 1.386 is uniform
over the four exits; near 0 one exit carries the whole loss and the cell has
stopped weighting its exits.  Beside it on standard error the mean mass of
the last exit and every exit's mean cross entropy, and which reading it
was."""

import json
import sys

from benchmarks import program_spans

NAMES = ("loop_exit_entropy", "loop_exit_mass_last", "loop_ce_by_step")


def read(observed):
    records = {name: program_spans.model_stats(observed, name)
               for name in NAMES}
    if records[NAMES[0]]:
        seen = {name: found[-1][1] for name, found in records.items() if found}
        origin = f"window step {records[NAMES[0]][-1][0]}"
    else:
        seen = getattr(observed.get("family"), "SEEN", None)
        origin = "forward check"
    if not seen or not seen.get(NAMES[0]):
        return None
    print(json.dumps({"phase": "loop_exits", "read_at": origin, **seen}),
          file=sys.stderr, flush=True)
    return seen[NAMES[0]][0]
