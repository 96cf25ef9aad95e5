#!/usr/bin/env python
"""Where does the dedup substep's time go? prologue vs kernel.

The dedup kernel moves ~3x fewer rows than grouped yet measures about
the same words/sec — chunked waits removed the wait-loop scalar ops, so
the remaining suspects are (a) the XLA prep prologue (argsort + cumsum +
scatter over [nblocks, cap] inside the jitted step) and (b) the one-hot
broadcast/accumulate compute chain. This times the full step vs a
prologue-only jit of the identical prep math on identical batches.

Run alone on the chip:  python tools/dedup_profile.py
"""

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from swiftsnails_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax
    import jax.numpy as jnp

    from swiftsnails_tpu.ops import fused_sgns as fs

    print(f"devices: {jax.devices()}", flush=True)

    V, DIM, W, PC, PN, UC = 1_000_000, 200, 5, 256, 64, 384
    S = -(-DIM // 128)
    # centers per KERNEL CALL — the cell's substep shape (the grouped batch
    # is capped at 8192 for SMEM; the macro is 8 scanned substeps).
    # 98304-as-one-call overflows the 1 MiB SMEM prefetch budget.
    N = 8192
    SPC = 8  # substeps per timed dispatch, matching STEPS_PER_CALL
    rng = np.random.default_rng(0)

    # zipf-ish corpus -> block-ordered window macro, as the bench builds;
    # split into SPC scanned substeps so the timed dispatch matches the
    # trainer's macro step (a single call would be dominated by dispatch)
    ranks = rng.zipf(1.2, size=900_000).astype(np.int64)
    ids = np.minimum(ranks - 1, V - 1).astype(np.int32)
    from swiftsnails_tpu.data import native as nat

    wp = nat.WindowPrefetcher(
        *nat.skipgram_windows(ids, W, seed=1), batch_size=N * SPC, block=PC,
        epochs=1, seed=1)
    batch = next(iter(wp))
    wp.close()
    cw = batch["contexts"].shape[1]
    cs = jnp.asarray(batch["centers"].reshape(SPC, N))
    xs = jnp.asarray(batch["contexts"].reshape(SPC, N, cw))
    ps = jnp.asarray(
        rng.integers(0, V, (SPC, (N // PC) * PN)).astype(np.int32))

    a = jnp.asarray(rng.random((V, S, 128), dtype=np.float32))
    b = jnp.zeros((V, S, 128), jnp.float32)

    # ---- prologue-only: the SHARED prep math, scanned like the trainer ----
    def make_prologue():
        # factory: a fresh function object per call gives a fresh jit cache
        # entry, so the --ab-prep impl switch below can never be masked by
        # a cached trace (the prep impl is read at trace time)
        @functools.partial(jax.jit, static_argnames=("pc", "u_cap"))
        def prologue(cs, xs, pc, u_cap):
            def body(acc, inp):
                c, x = inp
                outs = fs.dedup_prep(c, x, pc, u_cap)
                return acc + sum(o.astype(jnp.float32).sum() for o in outs), 0
            acc, _ = jax.lax.scan(body, jnp.float32(0), (cs, xs))
            return acc
        return prologue

    prologue = make_prologue()

    def macro(step_fn, **kw):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def run(a, b, cs, xs, ps):
            def body(carry, inp):
                a, b = carry
                c, x, p = inp
                a, b, loss = step_fn(
                    a, b, c, x, p, lr=0.025, lam=5 / PN, window=W,
                    centers_per_block=PC, pool_size=PN, **kw)
                return (a, b), loss
            (a, b), losses = jax.lax.scan(body, (a, b), (cs, xs, ps))
            return a, b, losses.sum()
        return run

    def timeit(name, fn, reps=10):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps
        print(f"{name}: {dt * 1e3:.2f} ms/macro "
              f"({N * SPC / dt:,.0f} words/sec-equiv)", flush=True)
        return dt

    t_pro = timeit("prologue only", lambda: prologue(cs, xs, pc=PC, u_cap=UC))

    if "--ab-prep" in sys.argv:
        # A/B the prep placement impls (scatter vs sort — the TPU lowering
        # cost of XLA scatter is the open question). set_prep_impl clears
        # the affected jit caches itself; the fresh prologue factory below
        # only exists because `prologue` is jitted here, not in fused_sgns.
        other = "sort" if fs.get_prep_impl() == "scatter" else "scatter"
        saved = fs.set_prep_impl(other)
        try:
            prologue_b = make_prologue()
            timeit(f"prologue only ({other} impl)",
                   lambda: prologue_b(cs, xs, pc=PC, u_cap=UC))
        finally:
            fs.set_prep_impl(saved)

    st = {}

    def run_macro(name, step_fn, **kw):
        st[name] = (a.copy(), b.copy())
        m = macro(step_fn, **kw)

        def go():
            na, nb, loss = m(st[name][0], st[name][1], cs, xs, ps)
            st[name] = (na, nb)
            return loss

        dt = timeit(name, go)
        del st[name]  # ~2 GB HBM per kernel's table pair; don't accumulate
        return dt

    t_ded = run_macro("dedup macro", fs.fused_sgns_dedup_step, u_cap=UC)
    t_grp = run_macro("grouped macro", fs.fused_sgns_grouped_step)

    if "--ab-prep" in sys.argv:
        # full-step A/B under the other impl. The step fn is itself @jit
        # with an aval-keyed trace cache; set_prep_impl clears it on switch
        # (both directions), so the "other" macro can never inline the
        # first impl's jaxpr and time the wrong thing.
        other = "sort" if fs.get_prep_impl() == "scatter" else "scatter"
        saved = fs.set_prep_impl(other)
        try:
            run_macro(f"dedup macro ({other} impl)",
                      fs.fused_sgns_dedup_step, u_cap=UC)
        finally:
            fs.set_prep_impl(saved)

    print(f"prologue share of dedup macro: {t_pro / t_ded * 100:.0f}% "
          f"(kernel-only implied: {N * SPC / (t_ded - t_pro):,.0f} w/s)",
          flush=True)

    if "--resident" in sys.argv:
        run_macro("resident macro", fs.fused_sgns_resident_step,
                  hot_rows=2048)
    if "--composed" in sys.argv:  # compile blowup suspect: time it visibly
        t0 = time.perf_counter()
        run_macro("composed macro", fs.fused_sgns_dedup_resident_step,
                  u_cap=UC, hot_rows=256)
        print(f"composed total incl. compile: {time.perf_counter() - t0:.0f}s",
              flush=True)


if __name__ == "__main__":
    main()
