"""The short-conv / expert backbone's whole training step as a share of the
chip's peak: the operations the forward and backward passes of the traced
epoch's samples require (``benchmark/counts_conv_moe.py``: padding counts,
recomputation does not, the routed experts' term from the program's
``routedPairs`` counter), over the peak bfloat16 rate of the cell's chips,
over the traced window's time. Nothing where the epoch's record has no
counters."""

from benchmark import counts_conv_moe
from benchmark.peaks import peaks_for
from benchmark.trainer_record import traced_epoch


def read(ctx):
    entry, config = ctx["entry"], ctx["config"]
    counters = (traced_epoch(ctx) or {}).get("counters")
    if not counters or "routedPairs" not in counters:
        return None
    seq = int(config["max_position_embeddings"])
    flops = counts_conv_moe.conv_moe_train_flops(
        config, counters["tokens"] // seq, seq, counters["routedPairs"],
        entry.classes)
    peak = peaks_for(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / peak / ctx["traced_s"]
