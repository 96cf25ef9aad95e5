"""Telemetry subsystem: span tracer, chrome export, and the compiled-HLO
communication audit (sync + async collective forms) on the 8-device mesh."""

import json
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swiftsnails_tpu.telemetry import (
    Tracer,
    audit_step,
    collective_bytes,
    collective_stats,
    compiled_collective_bytes,
)
from swiftsnails_tpu.parallel import SgdAccess, create_table, make_mesh
from swiftsnails_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, batch_sharding


# ------------------------------------------------------------- tracer ------


def test_tracer_nested_spans_and_export(tmp_path):
    path = str(tmp_path / "trace.json")
    tr = Tracer(path=path)
    with tr.span("outer", step=0):
        with tr.span("inner"):
            pass
    with tr.span("outer", step=1):
        pass
    tr.close()
    tr.close()  # idempotent: the same events again

    doc = json.load(open(path))
    assert "traceEvents" in doc  # chrome-loadable shape
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    outers = [e for e in evs if e["name"] == "outer"]
    inner = next(e for e in evs if e["name"] == "inner")
    assert len(outers) == 2
    assert outers[0]["args"] == {"step": 0}
    # time containment: inner nests inside its outer
    o = outers[0]
    assert o["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= o["ts"] + o["dur"] + 1e-3
    # depth bookkeeping survives exceptions
    with pytest.raises(RuntimeError):
        with tr.span("erring"):
            raise RuntimeError("boom")
    assert getattr(tr._tls, "depth", 0) == 0


def test_tracer_threads_record_independently():
    tr = Tracer()
    barrier = threading.Barrier(3)

    def work():
        barrier.wait()
        for _ in range(50):
            with tr.span("worker"):
                pass

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    barrier.wait()
    for _ in range(50):
        with tr.span("main"):
            pass
    for t in threads:
        t.join()
    evs = tr.events()
    assert sum(e["name"] == "worker" for e in evs) == 100
    assert sum(e["name"] == "main" for e in evs) == 50
    assert len({e["tid"] for e in evs}) >= 2


# ----------------------------------------------- HLO audit: text parsing ---


SYNC_HLO = """
  %ar = f32[128,8]{1,0} all-reduce(f32[128,8]{1,0} %p), channel_id=1, metadata={op_name="jit(step)/ssn_pull_collective/psum" source_file="x.py"}
  %ag = f32[64,16]{1,0} all-gather(f32[8,16]{1,0} %q), channel_id=2, metadata={op_name="jit(step)/ssn_push_collective/all_gather"}
  %use = f32[128,8]{1,0} add(f32[128,8]{1,0} %ar, f32[128,8]{1,0} %ar)
"""

ASYNC_HLO = """
  %ars = f32[128,8]{1,0} all-reduce-start(f32[128,8]{1,0} %p), channel_id=1
  %ard = f32[128,8]{1,0} all-reduce-done(f32[128,8]{1,0} %ars)
  %ags = (f32[8,16]{1,0}, f32[64,16]{1,0}) all-gather-start(f32[8,16]{1,0} %q), channel_id=2
  %agd = f32[64,16]{1,0} all-gather-done((f32[8,16]{1,0}, f32[64,16]{1,0}) %ags)
"""


def test_collective_stats_sync_form():
    st = collective_stats(SYNC_HLO)
    assert st["ops"]["all-reduce"] == {"count": 1, "bytes": 128 * 8 * 4}
    assert st["ops"]["all-gather"] == {"count": 1, "bytes": 64 * 16 * 4}
    assert st["total_bytes"] == 128 * 8 * 4 + 64 * 16 * 4
    # the consumer `add` line referencing %ar is not double counted, and the
    # named_scope labels attribute bytes per pull/push path
    assert st["by_scope"] == {
        "ssn_pull_collective": 128 * 8 * 4,
        "ssn_push_collective": 64 * 16 * 4,
    }


def test_collective_stats_async_form_matches_sync():
    """The ADVICE r5 bug: async pairs must report the same traffic as the
    sync forms, with -done halves never counted."""
    sync = collective_stats(SYNC_HLO)
    asyn = collective_stats(ASYNC_HLO)
    assert asyn["ops"]["all-reduce"] == sync["ops"]["all-reduce"]
    assert asyn["ops"]["all-gather"] == sync["ops"]["all-gather"]
    assert asyn["total_bytes"] == sync["total_bytes"]


def test_collective_bytes_pattern_filter():
    assert collective_bytes(ASYNC_HLO, "all-gather") == 64 * 16 * 4
    assert collective_bytes(ASYNC_HLO, "all-reduce") == 128 * 8 * 4
    assert (
        collective_bytes(ASYNC_HLO, "all-gather|all-reduce")
        == collective_bytes(ASYNC_HLO)
    )
    assert collective_bytes(SYNC_HLO, "reduce-scatter") == 0


def test_collective_stats_dtype_aware():
    hlo = "%x = bf16[32,4]{1,0} all-gather(bf16[4,4]{1,0} %a), channel_id=3"
    st = collective_stats(hlo)
    assert st["ops"]["all-gather"]["bytes"] == 32 * 4 * 2


def test_reduce_scatter_bills_full_operand():
    """A sync reduce-scatter's result is the 1/N scattered slice; the wire
    moved the FULL operand, so billing must take the operand side."""
    hlo = ("%rs = f32[16,8]{1,0} reduce-scatter(f32[64,8]{1,0} %x), "
           "channel_id=4, metadata={op_name=\"jit(step)/ssn_zero_head_push"
           "/psum_scatter\"}")
    st = collective_stats(hlo)
    assert st["ops"]["reduce-scatter"] == {"count": 1, "bytes": 64 * 8 * 4}
    assert st["by_scope"] == {"ssn_zero_head_push": 64 * 8 * 4}
    # the text jax 0.9.0 prints names the operand without its shape: the
    # operand's own defining line in the same computation supplies it, and
    # a same-named instruction of ANOTHER computation does not
    named = (
        "%fused (p: f32[4096,8]) -> f32[4096,8] {\n"
        "  %bitcast = f32[4096,8]{1,0} bitcast(%p)\n"
        "}\n"
        "ENTRY %main (x: f32[64,8]) -> f32[16,8] {\n"
        "  %bitcast = f32[64,8]{1,0} bitcast(%x)\n"
        "  ROOT %rs.7 = f32[16,8]{1,0} reduce-scatter(%bitcast), "
        "channel_id=1, replica_groups={{0,2,4,6},{1,3,5,7}}, dimensions={0}, "
        "to_apply=%region_0.0, metadata={op_name=\"jit(step)/shard_map/"
        "ssn_zero_head_push/reduce_scatter\"}\n"
        "}\n")
    st = collective_stats(named)
    assert st["ops"]["reduce-scatter"] == {"count": 1, "bytes": 64 * 8 * 4}
    assert st["by_scope"] == {"ssn_zero_head_push": 64 * 8 * 4}


def test_reduce_scatter_sub_byte_operand():
    # int4 wire: (n * bits + 7) // 8, measured on the full operand
    hlo = "%rs = u4[16,8]{1,0} reduce-scatter(u4[64,8]{1,0} %x), channel_id=4"
    st = collective_stats(hlo)
    assert st["ops"]["reduce-scatter"]["bytes"] == (64 * 8 * 4 + 7) // 8


def test_all_to_all_tuple_sums_pieces():
    """Tiled shard_map all_to_all lowers to the tuple form with axis_size
    operand/result pieces — the bill is the sum, not the max element."""
    hlo = ("%a2a = (f32[8,4]{1,0}, f32[8,4]{1,0}, f32[8,4]{1,0}, "
           "f32[8,4]{1,0}) all-to-all(f32[8,4]{1,0} %p0, f32[8,4]{1,0} %p1, "
           "f32[8,4]{1,0} %p2, f32[8,4]{1,0} %p3), channel_id=5")
    st = collective_stats(hlo)
    assert st["ops"]["all-to-all"] == {"count": 1, "bytes": 4 * 8 * 4 * 4}


def test_all_to_all_async_start_not_double_billed():
    """-start forms carry operand AND result aliases in one tuple; the
    halving keeps async traffic equal to the sync form's."""
    sync = ("%a = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-to-all("
            "f32[8,4]{1,0} %p0, f32[8,4]{1,0} %p1), channel_id=6")
    asyn = ("%s = ((f32[8,4]{1,0}, f32[8,4]{1,0}), (f32[8,4]{1,0}, "
            "f32[8,4]{1,0})) all-to-all-start(f32[8,4]{1,0} %p0, "
            "f32[8,4]{1,0} %p1), channel_id=6\n"
            "%d = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-to-all-done(%s)")
    st_sync = collective_stats(sync)
    st_asyn = collective_stats(asyn)
    assert st_sync["ops"]["all-to-all"]["bytes"] == 2 * 8 * 4 * 4
    assert st_asyn["ops"]["all-to-all"] == st_sync["ops"]["all-to-all"]


# ------------------------------------- audit of a real sharded step -------


def _sharded_pull_push(mesh):
    from swiftsnails_tpu.parallel.transfer import pull_collective, push_collective

    access = SgdAccess()
    state = create_table(64, 8, access, mesh=mesh, seed=0)
    rng = np.random.default_rng(0)
    bs = batch_sharding(mesh)
    rows = jax.device_put(rng.integers(0, 64, 16).astype(np.int32), bs)
    grads = jax.device_put(rng.normal(size=(16, 8)).astype(np.float32), bs)

    def step(state, rows, grads):
        vals = pull_collective(mesh, state, rows)
        return push_collective(mesh, state, rows, grads + vals * 1e-6, access, 0.1).table

    return step, (state, rows, grads)


def test_audit_sharded_pull_push_nonzero_bytes():
    """Acceptance: the audit reports nonzero collective bytes for a sharded
    pull/push step function, attributed per pull/push scope label."""
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    step, args = _sharded_pull_push(mesh)
    report = audit_step(step, *args)
    assert report["total_bytes"] > 0
    assert sum(e["count"] for e in report["ops"].values()) >= 2
    # pull psum and push all_gather both show up under their labels
    assert report["by_scope"].get("ssn_pull_collective", 0) > 0
    assert report["by_scope"].get("ssn_push_collective", 0) > 0
    # memory analysis is present (cost may be backend-limited but not fatal)
    assert "memory" in report and "cost" in report


def test_compiled_collective_bytes_kernel_lab_contract():
    """The promoted kernel_lab helper: same signature, op_pattern filter."""
    mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
    step, args = _sharded_pull_push(mesh)
    both = compiled_collective_bytes(step, args, "all-gather|all-reduce")
    ar_only = compiled_collective_bytes(step, args, "all-reduce")
    assert both > 0
    assert 0 < ar_only <= both
    # and kernel_lab's module-level wrapper delegates here
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "kernel_lab",
        os.path.join(os.path.dirname(__file__), "..", "tools", "kernel_lab.py"),
    )
    kl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kl)
    assert kl._compiled_collective_bytes(step, args, "all-reduce") == ar_only


def test_audit_compiled_reduce_scatter_full_operand():
    """End to end on real compiled HLO: an f32 reduce_scatter_quantized
    step bills the full operand under its ssn_zero scope label."""
    from jax.sharding import PartitionSpec as P

    from swiftsnails_tpu.parallel.comm import reduce_scatter_quantized

    mesh = make_mesh({DATA_AXIS: 4, MODEL_AXIS: 2})
    rows, dim = 64, 8

    def step(x):
        def body(xs):
            with jax.named_scope("ssn_zero_head_push"):
                return reduce_scatter_quantized(xs[0], DATA_AXIS, "float32", 4)

        return jax.shard_map(body, mesh=mesh, in_specs=(P(DATA_AXIS),),
                             out_specs=P(DATA_AXIS), check_vma=False)(x)

    report = audit_step(step, jnp.ones((4, rows, dim), jnp.float32))
    assert report["ops"]["reduce-scatter"]["bytes"] == rows * dim * 4
    assert report["by_scope"].get("ssn_zero_head_push", 0) == rows * dim * 4


def test_audit_single_device_no_collectives():
    def f(x):
        return (x * 2).sum()

    report = audit_step(f, jnp.ones((8, 8)))
    assert report["total_bytes"] == 0
    assert report["ops"] == {}
