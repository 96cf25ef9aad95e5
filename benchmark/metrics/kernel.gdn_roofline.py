"""The chunked delta rule's share of its roofline where Gated DeltaNet calls
it, which is compute: the matrix-product operations its calls of the window's
steps need (forward, the rematerialised forward and the backward of every
such layer; the model's file counts them at the published key and value
widths) over the chip's peak bf16 FLOP/s, over the device time of the
operations under the scope ``phase_gdn_core`` (``ops/gated_delta.py``: the
two kernels and what XLA does at the call's edge), both over the whole
measured window. A program without that scope, or a model without the count,
gives no reading."""

from lib import peaks, scopes, spans, trace

CORE = "phase_gdn_core"


def read(run):
    model = run.model
    profile = run.extra.get("profile")
    if (not hasattr(model, "gdn_kernel_flops_per_step") or run.trace is None or profile is None
            or profile.window is None):
        return None
    steps = spans.steps_in(run, run.t0, run.t1)
    planes = scopes.load_scoped(trace.find_xplane(profile.dir))
    if not steps or not planes:
        return None
    w0 = profile.window[0]
    inside = scopes.own_seconds(planes, (w0, w0 + round(run.window_s * 1e9)),
                                lambda name, scope, phase: CORE in scope).get(True, 0.0)
    if inside <= 0:
        return None
    least = (model.gdn_kernel_flops_per_step(run.config["keys"]) * steps
             / peaks.peaks_for(run.device["kind"])["bf16_flops_per_s"])
    return peaks.share_pct(least, inside, "kernel.gdn_roofline")
