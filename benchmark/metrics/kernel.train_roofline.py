"""The training kernels' share of their roofline, which is memory: the least
time the chip needs for the table bytes the traced steps must read and write
(rows touched x stored row bytes / HBM bytes per second; the model's file
says how many per item), over the device time of the step's kernels in the
trace (names in the configuration's file)."""

from lib import peaks, trace


def read(run):
    if run.trace is None:
        return None
    seconds = trace.kernel_seconds(run.trace, run.config["kernels"]["train"])
    if seconds <= 0:
        return None
    rate = run.end_to_end["train_items_per_s"]
    need = run.model.bytes_per_item(run.config["keys"], run.config["stored_row_bytes"])
    least = need * rate * run.trace["window_s"] / peaks.peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    return peaks.share_pct(least, seconds, "kernel.train_roofline")
