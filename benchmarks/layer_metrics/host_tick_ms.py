"""Median ``trainer.step.tick`` of the window: what the stepping thread does
every ``DLROVER_TPU_DIGEST_EVERY`` steps (polls, memory sample, digests and
their file, the read of the model's sown ``stats``).  The longest and the
parts' medians are in the ``phase: step_ledger`` line."""

from benchmarks import step_ledger


def read(observed):
    return step_ledger.metric(observed, step_ledger.host_tick_ms)
