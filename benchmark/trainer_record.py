"""What the trainer's readers share: the trainer's own record of the traced
epoch (its ``history`` entry, caught by the entry from the estimator's
``epoch`` log record), or None where the cell's entry keeps none."""

from __future__ import annotations


def traced_epoch(ctx):
    return getattr(ctx["entry"], "traced_epoch", lambda: None)()


def share_of_epoch(ctx, key: str):
    """100 x the epoch's ``key`` seconds over the epoch's seconds."""
    epoch = traced_epoch(ctx)
    if not epoch or key not in epoch or not epoch.get("seconds"):
        return None
    return 100.0 * epoch[key] / epoch["seconds"]
