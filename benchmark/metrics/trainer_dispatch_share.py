"""The traced epoch's ``trainer.step/dispatch`` spans (the ``train_step``
call, until it returns its futures) over the epoch's seconds, both from the
trainer's own record of that epoch."""

from benchmark.trainer_record import share_of_epoch


def read(ctx):
    return share_of_epoch(ctx, "dispatch_s")
