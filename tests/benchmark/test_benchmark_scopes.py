"""The readers PR 26 added, on the CPU: the phase split of a recorded device
plane (``benchmark/data/trace_scopes_small.json``) against another way of
computing it, the xplane wire reader on a file made by hand, and each of the
twelve readers on a hand-made ``Run`` - also on one from a program that has
none of the spans and scopes, which must read as nothing and raise nothing."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, HERE)
import tiny_tree  # noqa: E402

NEW = ["entry.build_trainer_s", "entry.data_load_s", "train.producer_busy_share",
       "train.h2d_share", "train.finalize_s", "step.device_ms", "step.prep_ms",
       "step.fused_ms", "step.pull_ms", "step.push_ms", "step.dense_ms", "step.unscoped_ms"]


@pytest.fixture(scope="module")
def bench_run():
    """benchmark/run.py, imported (it puts benchmark/ on the path for ``lib``)."""
    spec = importlib.util.spec_from_file_location("bench_run_scopes", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def scopes(bench_run):
    from lib import scopes

    return scopes


# ------------------------------------------- the recorded device plane ---


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "data", "trace_scopes_small.json")) as f:
        rec = json.load(f)
    ops = [[name, s, d, rec["scopes"][i]] for name, s, d, i in rec["events"]]
    return rec, {rec["plane"]: ops}, tuple(rec["window"])


def _innermost_by_sweep(ops, w0, w1, labels):
    """{label: ns}: every stretch between two boundaries goes to the event
    that covers it and started last (the shorter of two that start together):
    another method than the reduction's stack. ``labels``: one per event."""
    clipped = [(max(s, w0), min(s + d, w1), s, d, lab)
               for (name, s, d, scope), lab in zip(ops, labels) if min(s + d, w1) > max(s, w0)]
    cuts = sorted({t for a, b, *_ in clipped for t in (a, b)})
    out = {}
    for lo, hi in zip(cuts, cuts[1:]):
        cover = [c for c in clipped if c[0] <= lo and c[1] >= hi]
        if cover:
            inner = max(cover, key=lambda c: (c[2], -c[3]))
            out[inner[4]] = out.get(inner[4], 0) + hi - lo
    return out


def test_phase_times_add_up_to_the_busy_union(scopes, recorded):
    rec, planes, window = recorded
    ops = planes[rec["plane"]]
    got = scopes.phase_seconds(planes, window)
    by_sweep = _innermost_by_sweep(ops, *window, [p or scopes.UNSCOPED for p in scopes.phases(ops)])
    assert set(got) == set(by_sweep) == {"prep", "fused", scopes.UNSCOPED}
    for phase, ns in by_sweep.items():
        assert got[phase] == pytest.approx(ns / 1e9, rel=1e-12), phase
    # the busy union, counted another way again: open intervals at every endpoint
    points = []
    for _, s, d, _ in ops:
        a, b = max(s, window[0]), min(s + d, window[1])
        if b > a:
            points += [(a, 1), (b, -1)]
    busy = depth = 0
    last = None
    for t, step in sorted(points):
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    assert sum(got.values()) == pytest.approx(busy / 1e9, rel=1e-12)
    for phase, want in rec["expected"]["phase_ns"].items():
        assert got[phase] * 1e9 == pytest.approx(want, rel=1e-9), phase


def test_a_while_is_counted_for_what_its_body_leaves(scopes, recorded):
    rec, planes, window = recorded
    ops = planes[rec["plane"]]
    whiles = [o for o in ops if o[0].startswith("while")]
    assert whiles, "the recorded plane holds the scan's while"
    name, s, d, scope = max(whiles, key=lambda o: o[2])
    a, b = max(s, window[0]), min(s + d, window[1])
    inside = [o for o in ops if o[1] > s and o[1] + o[2] <= s + d]
    assert len(inside) > 50  # its body's operations sit on the same line
    by_name = scopes.own_seconds(planes, window, lambda n, sc, ph: n)
    assert by_name[name] * 1e9 < 0.02 * (b - a)  # not the whole 9 ms again
    # it carries no phase and has no neighbour: the time it keeps is unscoped
    assert scopes.phases(ops)[ops.index(whiles[0])] is None
    assert by_name[name] <= scopes.phase_seconds(planes, window)[scopes.UNSCOPED]


def test_an_operation_xla_left_without_a_name_takes_its_neighbours_phase(scopes, recorded):
    """On the recorded plane: the scatter and cumsum expansions carry no
    op-name and run between prep operations; by their own scope they would
    be most of the unscoped time, and the prologue's largest part."""
    rec, planes, window = recorded
    ops = planes[rec["plane"]]
    ph = scopes.phases(ops)
    own = [scopes.phase_of(o[3]) for o in ops]
    adopted = {o[0] for o, mine, got in zip(ops, own, ph) if mine is None and got}
    assert set(rec["expected"]["adopted"]) <= adopted
    assert all(got == mine for mine, got in zip(own, ph) if mine)  # a phase of its own is kept
    assert {got for o, mine, got in zip(ops, own, ph) if mine is None and got} == {"prep"}
    by_own = _innermost_by_sweep(ops, *window, [p or scopes.UNSCOPED for p in own])
    for phase, want in rec["expected"]["own_scope_only_ns"].items():
        assert by_own[phase] == pytest.approx(want, rel=1e-9)
    got = scopes.phase_seconds(planes, window)
    assert got["unscoped"] * 1e9 < 0.25 * by_own["unscoped"] and got["fused"] * 1e9 == pytest.approx(by_own["fused"])
    # what is left unscoped: the while's own gaps and the scan's slicing between kernel and draw
    # (and an operation at the excerpt's very end, whose later neighbour is cut off)
    left = [o for o, got in zip(ops, ph) if got is None]
    assert "while.4" in {o[0] for o in left}
    assert all(o[1] > window[1] - 100_000 for o in left if o[0] in rec["expected"]["adopted"])


@pytest.mark.parametrize("seq,want", [
    # (phase of its own or None) in time order, all directly on the line
    (["prep", None, None, "prep"], ["prep", "prep", "prep", "prep"]),
    (["fused", None, "prep"], ["fused", None, "prep"]),        # between two phases: nobody's
    ([None, "prep", None], [None, "prep", None]),              # no neighbour on one side
    (["pull", None, "pull", None, "push", None, "push"], ["pull", "pull", "pull", None, "push", "push", "push"]),
])
def test_neighbour_rule_on_a_flat_line(scopes, seq, want):
    ops = [[f"op.{i}", 100 * i, 90, f"jit(_step)/phase_{p}/x" if p else "jit(_step)/while"]
           for i, p in enumerate(seq)]
    assert scopes.phases(ops) == want
    assert scopes.phases(ops[::-1]) == want[::-1]  # by time, whatever the order given


def test_neighbour_rule_keeps_to_one_nesting_level(scopes):
    ops = [["a", 0, 100, "phase_prep/x"],
           ["while.1", 100, 1000, "jit(_step)/while"],       # between prep and prep: adopted
           ["k", 150, 300, "phase_fused/k"],                 # its body is another level
           ["nameless", 500, 100, ""],                       # fused before, nothing after: unscoped
           ["b", 1100, 100, "phase_prep/y"],
           ["tail", 1200, 50, ""]]
    assert scopes.phases(ops) == ["prep", "prep", "fused", None, "prep", None]


@pytest.mark.parametrize("scope,want", [
    ("jit(_step)/phase_pull/ssn_pull_packed_small/jit(gather_rows)/pallas_call", "pull"),
    ("jit(_step)/while/body/closed_call/jit(fused_sgns_grouped_step)/phase_fused/fused_sgns_grouped_step/pallas_call", "fused"),
    ("jit(_step)/phase_dense/transpose(jvp())/dot_general", "dense"),
    ("jit(_step)/transpose(jvp(phase_dense))/mul", "dense"),
    ("jit(_step)/phase_push/phase_prep/sort", "prep"),  # the innermost
    ("jit(_step)/while/body/dynamic_slice", None),
    ("", None),
])
def test_phase_of_a_scope_path(scopes, scope, want):
    assert scopes.phase_of(scope) == want


# --------------------------------------------------- the wire reader ---


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | bytes | str) pairs."""
    out = b""
    for no, v in fields:
        if isinstance(v, int):
            out += _varint(no << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(no << 3 | 2) + _varint(len(v)) + v
    return out


def _xplane(ops, with_scopes=True):
    """An XSpace: one TPU plane whose "XLA Ops" line holds ``ops`` ((name,
    offset_ps, dur_ps, scope) each), a module line over the same time, and a
    host plane. Scopes as the profiler writes them: the ``tf_op`` stat of the
    event's metadata, as a string or as a reference to a stat's name."""
    stat_meta = [_msg((1, 1), (2, _msg((1, 1), (2, "tf_op")))),
                 _msg((1, 2), (2, _msg((1, 2), (2, "hlo_category"))))]
    ev_meta, events = [], []
    for i, (name, off, dur, scope) in enumerate(ops, start=10):
        stats = [(5, _msg((1, 2), (5, "fusion")))]
        if with_scopes and scope and i % 2:
            stats.append((5, _msg((1, 1), (5, scope + ":"))))
        elif with_scopes and scope:  # by reference: the string is a stat metadata's name
            stat_meta.append(_msg((1, 100 + i), (2, _msg((1, 100 + i), (2, scope + ":")))))
            stats.append((5, _msg((1, 1), (7, 100 + i))))
        ev_meta.append(_msg((1, i), (2, _msg((1, i), (2, name), *stats))))
        events.append(_msg((1, i), (2, off), (3, dur)))
    ops_line = _msg((1, 1), (2, "XLA Ops"), (3, 1_000), *[(4, e) for e in events])
    mod_meta = _msg((1, 5), (2, _msg((1, 5), (2, "jit__step(1)"))))
    mod_line = _msg((1, 2), (2, "XLA Modules"), (3, 1_000), (4, _msg((1, 5), (2, 0), (3, 10**9))))
    device = _msg((1, 1), (2, "/device:TPU:0"), (3, mod_line), (3, ops_line),
                  *[(4, m) for m in ev_meta + [mod_meta]], *[(5, m) for m in stat_meta])
    host = _msg((1, 2), (2, "/host:CPU"),
                (3, _msg((2, "main"), (4, _msg((1, 5), (2, 0), (3, 500))))))
    return _msg((1, device), (1, host))


OPS = [  # (hlo line, offset ps, duration ps, scope)
    ("%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p)", 0, 2_000_000, "jit(_step)/phase_prep/sort"),
    ("%while.4 = (s32[]) while((s32[]) %t), body=%b", 2_000_000, 20_000_000, "jit(_step)/while"),
    ("%k.7 = f32[8,2,128]{2,1,0} custom-call(%a), custom_call_target=\"tpu_custom_call\"",
     3_000_000, 12_000_000, "jit(_step)/while/body/phase_fused/k/pallas_call"),
    ("%fusion.9 = f32[8]{0} fusion(%x)", 16_000_000, 5_000_000, "jit(_step)/while/body/phase_prep/mul"),
    ("%copy.1 = f32[8]{0} copy(%y)", 23_000_000, 1_000_000, ""),
]


def _write(tmp_path, raw, name="t"):
    d = tmp_path / name / "plugins" / "profile" / "2026_09_30"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    return str(tmp_path / name)


def test_wire_reader_gives_names_times_and_scopes(scopes, tmp_path):
    from lib import trace

    path = trace.find_xplane(_write(tmp_path, _xplane(OPS)))
    planes = scopes.load_scoped(path)
    assert list(planes) == ["/device:TPU:0"]  # not the host plane, not the module line
    got = planes["/device:TPU:0"]
    assert [g[0] for g in got] == ["fusion.3", "while.4", "k.7 tpu_custom_call", "fusion.9", "copy.1"]
    assert [g[1:3] for g in got] == [[1000 + o // 1000, d // 1000] for _, o, d, _ in OPS]
    assert [g[3] for g in got] == [s for *_, s in OPS]
    ph = scopes.phase_seconds(planes, (1000, 1000 + 24_000))
    # the while sits between prep and nothing, the copy after it has no later
    # neighbour: the while keeps 20 - 12 - 5, the copy 1
    assert ph == {"prep": pytest.approx(7e-6), "fused": pytest.approx(12e-6),
                  "unscoped": pytest.approx(4e-6)}
    # jax reads the same file to the same names and times
    neutral = trace.load_xplane(path)
    assert neutral["device"]["/device:TPU:0"] == [g[:3] for g in got]


# ------------------------------------------- readers on a made-up Run ---


class _Profile:
    def __init__(self, directory, window):
        self.dir, self.window = directory, window


def _run(bench_run, tmp_path, new_program=True):
    """A traced run of 4.0 s with four steps, as the job would leave it."""
    from lib import jobs

    t0 = 100.0
    spans = [("prefetch-wait", t0 + 0.1, 0.04), ("step", t0 - 5.0, 2.0)]  # a warm step, before
    for i in range(4):  # the window opens inside the first step's span
        spans += [("h2d", t0 + i, 0.02), ("step", t0 + i - 0.05, 0.3)]
    if new_program:
        spans += [("build-trainer", 80.0, 8.0), ("load-data", 80.5, 5.0), ("alias-table", 86.0, 1.5),
                  ("produce", t0 + 0.5, 0.25), ("produce", t0 + 1.5, 0.15), ("produce", 90.0, 3.0),
                  ("queue-full", t0 + 0.8, 0.7), ("finalize", t0 + 3.6, 0.01),
                  ("drain", t0 + 3.61, 0.3), ("finalize", t0 + 3.95, 0.03)]
    ops = [(n, o * 1000, d * 1000, s) for n, o, d, s in [
        ("%a.1 = s32[8]{0} fusion(%p)", 0, 400_000_000, "jit(_step)/phase_prep/sort"),
        ("%g.1 = f32[8]{0} custom-call(%p), custom_call_target=\"tpu_custom_call\"",
         400_000_000, 1_200_000_000, "jit(_step)/phase_pull/jit(gather_rows)/pallas_call"),
        ("%m.1 = f32[8]{0} fusion(%p)", 1_600_000_000, 200_000_000, "jit(_step)/phase_dense/jvp()/dot_general"),
        ("%s.1 = f32[8]{0} custom-call(%p), custom_call_target=\"tpu_custom_call\"",
         1_800_000_000, 2_000_000_000, "jit(_step)/phase_push/jit(scatter)/pallas_call"),
        ("%f.1 = f32[8]{0} custom-call(%p), custom_call_target=\"tpu_custom_call\"",
         3_800_000_000, 100_000_000, "jit(_step)/phase_fused/k/pallas_call"),
        ("%c.1 = f32[8]{0} copy(%p)", 3_900_000_000, 40_000_000, ""),
        ("%late.1 = f32[8]{0} copy(%p)", 4_100_000_000, 500_000_000, "jit(_step)/phase_prep/x"),  # after t1
    ]]
    directory = _write(tmp_path, _xplane(ops, with_scopes=new_program), "new" if new_program else "old")
    run = jobs.Run(config={}, mix={}, seed=1, seconds=2.0, traced=True,
                   device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    run.t0, run.t1, run.spans = t0, t0 + 4.0, spans
    run.trace = {"busy_s": 1.9, "window_s": 2.0}  # the cut window's reduction; not what scopes reads
    run.extra = {"profile": _Profile(directory, (1000, 1000 + 2 * 10**9))}
    return run


WANT = {  # per step: device ns of the window over four steps
    "entry.build_trainer_s": 8.0, "entry.data_load_s": 5.0,
    "train.producer_busy_share": 100 * 0.4 / 4.0, "train.h2d_share": 100 * 0.08 / 4.0,
    "train.finalize_s": 0.04,
    "step.device_ms": 3940 / 4, "step.prep_ms": 400 / 4, "step.fused_ms": 100 / 4,
    "step.pull_ms": 1200 / 4, "step.push_ms": 2000 / 4, "step.dense_ms": 200 / 4,
    "step.unscoped_ms": 40 / 4,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_made_up_run(bench_run, tmp_path, name):
    run = _run(bench_run, tmp_path)
    assert bench_run.load_reader(name)(run) == pytest.approx(WANT[name], rel=1e-9)
    if name.startswith("step."):
        got = run.extra["scopes"]
        assert got["steps"] == 4  # the warm step before the window is not one of them
        assert sum(got["phase_ms"].values()) == pytest.approx(got["device_ms"])
        os.remove(os.path.join(run.extra["profile"].dir, "plugins", "profile", "2026_09_30", "host.xplane.pb"))
        assert bench_run.load_reader(name)(run) == pytest.approx(WANT[name])  # computed once per run


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_program_without_the_spans_and_scopes(bench_run, tmp_path, name):
    """The parent of PR 26 under this benchmark: nothing to read is no value,
    and no error; the device time per step it can give, it gives."""
    run = _run(bench_run, tmp_path, new_program=False)
    got = bench_run.load_reader(name)(run)
    if name == "step.device_ms":
        assert got == pytest.approx(3940 / 4)
    elif name == "train.h2d_share":
        assert got == pytest.approx(2.0)  # the loop of old had that span
    else:
        assert got is None
    rehearsal = _run(bench_run, tmp_path / "cpu", new_program=False)
    rehearsal.trace = None  # no device plane was reduced
    if name.startswith("step."):
        assert bench_run.load_reader(name)(rehearsal) is None


# --------------------------------------------------- the entries ---


def test_new_entries_resolve_and_the_tiny_tree_still_builds(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-12:] == NEW  # appended, in the issue's order
    cells = {w["name"] for w in bench["workloads"]}
    layers = {m["layer"] for m in bench["per_layer"][:6]}
    for name in NEW:
        m = by_name[name]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["better"] == "lower" and m["layer"] in layers and set(m["workloads"]) <= cells
        assert m["source"] == ("device_trace" if name.startswith("step.") else "program_span")
        assert m["moves"] == ("setup_s" if name.startswith("entry.") else "train_items_per_s")
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
    assert by_name["step.fused_ms"]["workloads"] == ["w2v-enwiki-200.train"]
    for name in ("step.pull_ms", "step.push_ms", "step.dense_ms"):
        assert by_name[name]["workloads"] == ["widedeep-criteo.train"]
    tree = tiny_tree.build(str(tmp_path / "tree"))
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        tiny = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert tiny["step.fused_ms"]["workloads"] == ["tiny-w2v.tiny-train", "tiny-logreg.tiny-train-again"]
    assert all(os.path.isfile(os.path.join(tree, "benchmark", "metrics", n + ".py")) for n in NEW)
