"""The stepping thread's time runnable and not run (``run_delay_ns`` of the
window's ``trainer.step`` spans, from ``/proc/thread-self/schedstat``) over
the time its steps span: how contended the host is."""

from benchmarks import step_ledger


def read(observed):
    return step_ledger.metric(observed, step_ledger.host_run_delay_pct)
