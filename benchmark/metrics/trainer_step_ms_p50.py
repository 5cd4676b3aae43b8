"""The median ``trainer.epoch/trainer.step`` span of the traced epoch, as
the trainer's own record of that epoch gives it (``step_ms_p50``)."""

from benchmark.trainer_record import traced_epoch


def read(ctx):
    return (traced_epoch(ctx) or {}).get("step_ms_p50")
