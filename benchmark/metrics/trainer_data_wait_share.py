"""The traced epoch's ``trainer.step/dataWait`` spans (``next()`` on the
prefetch iterator) over the epoch's seconds, both from the trainer's own
record of that epoch: whether the host or the device paces the loop."""

from benchmark.trainer_record import share_of_epoch


def read(ctx):
    return share_of_epoch(ctx, "data_wait_s")
