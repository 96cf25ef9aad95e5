"""Share of the warm steps' tokens that the batch's noise draw masked: 50% give
or take the draw (one ``t ~ U(0, 1)`` a block of four); a feed that stopped
masking, or masked everything, shows here. From the ``noised`` count the
program's state carries under the block-diffusion objective."""


def read(run):
    diffusion = run.counters.get("diffusion")
    return None if diffusion is None else float(diffusion["masked_share_pct"])
