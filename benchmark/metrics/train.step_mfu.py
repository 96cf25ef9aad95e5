"""The whole training step's share of the chip's peak bf16 FLOP/s: the
operations the algorithm needs per item (the model's file says how many)
times items per second."""

from lib import peaks


def read(run):
    rate = run.end_to_end.get("train_items_per_s")
    if rate is None:
        return None
    per_item = run.model.flops_per_item(run.config["keys"])
    peak = peaks.peaks_for(run.device["kind"])["bf16_flops_per_s"] * run.device["count"]
    return peaks.share_pct(per_item * rate, peak, "train.step_mfu")
