"""The grouped expert products' share of their roofline, which is compute:
the operations the held (token, expert) assignments need (three products an
expert: forward, rematerialised forward, dx and dw; padding rows are not
counted) over the chip's peak bf16 FLOP/s, over the device time of
``grouped_matmul*``, both over the whole measured window. The assignments
are COUNTED, by the program, in the steps the kernels' time belongs to: the
job reads the state at the warm steps only, so the model's file runs the
window's steps once more and keeps each step's ``counts``
(``window_counts``; a traced run only, some 25 s after the reference)."""


def read(run):
    model = run.model
    if not hasattr(model, "window_counts") or run.trace is None:
        return None
    keys = run.config["keys"]
    lo = int(keys.get("expert_offset", 0))
    held = model.window_counts(run)[:, :, lo: lo + int(keys["experts_held"])]
    return model.kernel_roofline_pct(
        run, "experts", lambda steps: model.experts_kernel_flops(keys, float(held[:steps].sum())))
