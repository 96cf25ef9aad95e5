"""The attention kernels' share of their roofline, which is compute: the
operations the causal products of the window's steps need (forward, the
rematerialised forward, dq and dkv calls of every layer; the model's file
counts them) over the chip's peak bf16 FLOP/s, over the device time of
``flash_attention_fwd/dq/dkv``, both over the whole measured window."""


def read(run):
    model = run.model
    if not hasattr(model, "kernel_roofline_pct"):
        return None
    per_step = model.attention_kernel_flops_per_step(run.config["keys"])
    return model.kernel_roofline_pct(run, "attn", lambda steps: per_step * steps)
