"""The whole training step's share of the chip's peak: the operations the
forward and backward passes of the traced epoch's samples require
(``benchmark/counts.py``; recomputation counts nothing), over the peak
bfloat16 rate of the cell's chips, over the traced window's time (the
window's first epoch, from the trace's ``bench.window`` span)."""

from benchmark import counts
from benchmark.peaks import peaks_for
from benchmark.trainer_record import traced_epoch


def read(ctx):
    entry, config = ctx["entry"], ctx["config"]
    epoch = traced_epoch(ctx)
    if not epoch or "hidden_size" not in config:
        return None
    flops = counts.encoder_train_flops(
        int(epoch["steps"]) * entry.batch,
        config["max_position_embeddings"], config["hidden_size"],
        config["num_hidden_layers"], config["intermediate_size"],
        entry.classes)
    peak = peaks_for(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / peak / ctx["traced_s"]
