"""The readers PR 38 added, on the CPU: the split by part of a recorded device
plane (``benchmark/data/trace_parts_small.json``: one layer of Solar's traced
window) against the phase split of the same plane, the part of a scope path,
and each of the twelve readers on a hand-made ``Run`` - also on one whose
trace names phases and no parts (the parent's program), and on one that
recorded nothing, which must read as nothing and raise nothing."""

import importlib.util
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, HERE)
import tiny_tree  # noqa: E402
from test_benchmark_scopes import _Profile, _write, _xplane  # noqa: E402  (the hand-made xplane's wire)

LM = ["moonlight-16b-a3b.train-8k", "sdar-30b-a3b.train-bd-4k", "solar-open2-250b.train-4k"]
NEW = {  # metric -> (phase, part, layer, cells)
    "step.attn_in_ms": ("attn", "in", "model step", LM),
    "step.attn_out_ms": ("attn", "out", "model step", LM),
    "step.attn_core_ms": ("attn", "core", "kernels", LM),
    "step.experts_gather_ms": ("experts", "gather", "model step", LM),
    "step.experts_scatter_ms": ("experts", "scatter", "model step", LM),
    "step.experts_products_ms": ("experts", "products", "kernels", LM),
    "step.route_score_ms": ("route", "score", "model step", LM),
    "step.route_plan_ms": ("route", "plan", "model step", LM),
    "step.kda_in_ms": ("kda", "in", "model step", LM[2:]),
    "step.kda_out_ms": ("kda", "out", "model step", LM[2:]),
    "step.kda_core_ms": ("kda", "core", "kernels", LM[2:]),
    "step.unparted_ms": (None, None, "model step", LM),
}
PHASE_METRIC = {"attn": "step.attn_ms", "kda": "step.kda_ms", "experts": "step.experts_ms", "route": "step.route_ms"}


@pytest.fixture(scope="module")
def bench_run():
    return tiny_tree.import_run(BENCH, "bench_run_parts")


@pytest.fixture(scope="module")
def parts(bench_run):
    from lib import parts

    return parts


@pytest.fixture(scope="module")
def scopes(bench_run):
    from lib import scopes

    return scopes


# ------------------------------------------- the recorded device plane ---


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "data", "trace_parts_small.json")) as f:
        rec = json.load(f)
    ops = [[name, s, d, rec["scopes"][i]] for name, s, d, i in rec["events"]]
    return rec, {rec["plane"]: ops}, tuple(rec["window"])


def test_parts_and_unparted_add_up_to_the_phase(parts, scopes, recorded):
    rec, planes, window = recorded
    by_part = parts.part_seconds(planes, window)
    by_phase = scopes.phase_seconds(planes, window)
    assert {phase for phase, _ in by_part} == set(by_phase)
    for phase, whole in by_phase.items():
        mine = {part: s for (f, part), s in by_part.items() if f == phase}
        assert sum(mine.values()) == pytest.approx(whole, rel=1e-12), phase
        if phase not in parts.PARTED:
            assert set(mine) == {None}, phase  # mlp, head, opt and what is unscoped have no parts
    # the excerpt holds every part of the four phases, each as the chip read it
    named = {key for key in by_part if key[1]}
    assert named == {(f, p) for f, p, _, _ in NEW.values() if f}
    for key, want in rec["expected"]["part_ns"].items():
        phase, part = key.split("/")
        assert by_part[(phase, part if part != "-" else None)] * 1e9 == pytest.approx(want, rel=1e-9), key


def test_the_recorded_core_is_what_the_roofline_divides_by(parts, scopes, recorded):
    """``kernel.kda_roofline``'s own labelling (the string anywhere in the
    path) and the part's (the last token) find the same operations."""
    rec, planes, window = recorded
    by_string = scopes.own_seconds(planes, window, lambda name, scope, phase: "phase_kda_core" in scope)[True]
    assert parts.part_seconds(planes, window)[("kda", "core")] == pytest.approx(by_string, rel=1e-12)


def test_recorded_operations_without_a_part_are_few_and_named(parts, scopes, recorded):
    rec, planes, window = recorded
    ops = planes[rec["plane"]]
    bare = [(name, scope) for (name, _, _, scope), phase in zip(ops, scopes.phases(ops))
            if phase in parts.PARTED and parts.part_of(scope)[1] is None]
    # by kind (XLA's name without its number): the scheduler's asynchronous copies and slices, a scatter's
    # expansion, a loop's own gaps, and the slices of stacked leaves that ``stack`` makes outside every phase
    assert {re.sub(r"[.\d]+$", "", name) for name, _ in bare} == set(rec["expected"]["unparted"])
    assert all(not scope or "phase_" not in scope or parts.part_of(scope)[0] == "experts" for _, scope in bare)
    by_part = parts.part_seconds(planes, window)
    rest = sum(s for (phase, part), s in by_part.items() if part is None and phase in parts.PARTED)
    assert rest < 0.04 * sum(s for (phase, _), s in by_part.items() if phase in parts.PARTED)


@pytest.mark.parametrize("scope,want", [
    ("jit(_step)/jvp(phase_attn)/phase_attn_in/dot_general", ("attn", "in")),
    ("jit(_step)/transpose(jvp(jvp()))/checkpoint/phase_attn/phase_attn_core/flash_attention_dkv/pallas_call", ("attn", "core")),
    ("jit(_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/phase_attn/phase_attn_out/dot_general", ("attn", "out")),
    ("jit(_step)/jvp(phase_experts)/phase_route_plan/sort", ("route", "plan")),  # nested in another phase: the last token
    ("jit(_step)/checkpoint/phase_experts/phase_experts_gather/while/body/gather", ("experts", "gather")),
    ("jit(_step)/jvp(phase_kda)/phase_kda_core/while/body/dot_general", ("kda", "core")),  # PR 36's scope is a part
    ("jit(_step)/transpose(jvp(phase_kda_out))/mul", ("kda", "out")),
    ("jit(_step)/jvp(phase_experts)/sub", ("experts", None)),  # a bare phase: no part
    ("jit(_step)/phase_attn/phase_attn_in/phase_head/x", ("head", None)),  # the last token decides
    ("jit(_step)/phase_opt/adamw_step/mul", ("opt", None)),  # no third token
    ("jit(_step)/while/body/dynamic_slice", (None, None)),
    ("", (None, None)),
])
def test_part_of_a_scope_path(parts, scopes, scope, want):
    assert parts.part_of(scope) == want
    assert scopes.phase_of(scope) == want[0]  # the phase reads as before the parts


# ------------------------------------------- readers on a made-up Run ---

OPS = [  # (hlo line, offset us, duration us, scope); four steps in the window
    ("%f.1 = f32[8]{0} fusion(%p)", 0, 300, "jit(_step)/jvp(phase_attn)/phase_attn_in/dot_general"),
    ("%c.2 = f32[8]{0} copy(%p)", 300, 20, ""),  # XLA's, between two attn operations: attn, no part
    ("%k.3 = f32[8]{0} custom-call(%p), custom_call_target=\"tpu_custom_call\"", 320, 1000,
     "jit(_step)/jvp(phase_attn)/phase_attn_core/flash_attention_fwd/pallas_call"),
    ("%f.4 = f32[8]{0} fusion(%p)", 1320, 180, "jit(_step)/jvp(phase_attn)/phase_attn_out/add"),
    ("%f.5 = f32[8]{0} fusion(%p)", 1500, 90, "jit(_step)/jvp(phase_route)/phase_route_score/top_k"),
    ("%f.6 = s32[8]{0} fusion(%p)", 1590, 4, "jit(_step)/jvp(phase_experts)/sub"),  # the program's, outside its parts
    ("%s.7 = s32[8]{0} sort(%p)", 1594, 60, "jit(_step)/jvp(phase_experts)/phase_route_plan/sort"),
    ("%w.8 = (s32[]) while((s32[]) %t), body=%b", 1654, 110, "jit(_step)/jvp(phase_experts)/phase_experts_gather/while"),
    ("%g.9 = f32[8]{0} fusion(%p)", 1660, 100, "jit(_step)/jvp(phase_experts)/phase_experts_gather/while/body/gather"),
    ("%k.10 = f32[8]{0} custom-call(%p), custom_call_target=\"tpu_custom_call\"", 1764, 400,
     "jit(_step)/jvp(phase_experts)/phase_experts_products/grouped_matmul_swiglu/pallas_call"),
    ("%f.11 = f32[8]{0} fusion(%p)", 2164, 136, "jit(_step)/jvp(phase_experts)/phase_experts_scatter/scatter-add"),
    ("%f.12 = f32[8]{0} fusion(%p)", 2300, 200, "jit(_step)/jvp(phase_mlp)/dot_general"),
    ("%f.13 = f32[8]{0} fusion(%p)", 2500, 70, "jit(_step)/jvp(phase_kda)/phase_kda_in/dot_general"),
    ("%f.14 = f32[8]{0} fusion(%p)", 2570, 130, "jit(_step)/jvp(phase_kda)/phase_kda_core/triangular_solve"),
    ("%f.15 = f32[8]{0} fusion(%p)", 2700, 30, "jit(_step)/jvp(phase_kda)/phase_kda_out/dot_general"),
    ("%f.16 = f32[8]{0} fusion(%p)", 2730, 170, "jit(_step)/phase_opt/mul"),
    ("%late.1 = f32[8]{0} copy(%p)", 4_100_000, 500, "jit(_step)/phase_attn/phase_attn_in/x"),  # after t1
]
WANT = {  # us of the window over four steps, in ms a step
    "step.attn_in_ms": 300, "step.attn_core_ms": 1000, "step.attn_out_ms": 180,
    "step.experts_gather_ms": 110, "step.experts_products_ms": 400, "step.experts_scatter_ms": 136,
    "step.route_score_ms": 90, "step.route_plan_ms": 60,
    "step.kda_in_ms": 70, "step.kda_core_ms": 130, "step.kda_out_ms": 30,
    "step.unparted_ms": 24,
}
WANT = {k: v / 4 / 1e3 for k, v in WANT.items()}


def _strip_parts(scope):
    """The same path as the parent's program writes it: phases, no parts
    (its one inner scope, ``phase_kda_core``, it has)."""
    return re.sub(r"/phase_(?!kda_core)[a-z]+_[a-z]+", "", scope).replace(
        "jvp(phase_experts)/sort", "jvp(phase_experts)/phase_route/sort")


def _run(tmp_path, program="change"):
    """A traced run of 4.0 s with four steps, as the job would leave it."""
    from lib import jobs

    t0 = 100.0
    spans = [("step", t0 - 5.0, 2.0)] + [("step", t0 + i - 0.05, 0.3) for i in range(4)]
    ops = [(n, o * 10**6, d * 10**6, s if program == "change" else _strip_parts(s)) for n, o, d, s in OPS]
    if program == "before-pr36":  # no inner scope at all
        ops = [(n, o, d, s.replace("/phase_kda_core", "")) for n, o, d, s in ops]
    run = jobs.Run(config={}, mix={}, seed=1, seconds=2.0, traced=True,
                   device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    run.t0, run.t1, run.spans = t0, t0 + 4.0, spans
    run.trace = {"busy_s": 1.9, "window_s": 2.0}
    run.extra = {"profile": _Profile(_write(tmp_path, _xplane(ops), program), (1000, 1000 + 2 * 10**9))}
    return run


@pytest.mark.parametrize("name", list(NEW))
def test_reader_on_a_made_up_run(bench_run, tmp_path, name):
    run = _run(tmp_path)
    assert bench_run.load_reader(name)(run) == pytest.approx(WANT[name], rel=1e-9)
    got = run.extra["parts"]
    os.remove(os.path.join(run.extra["profile"].dir, "plugins", "profile", "2026_09_30", "host.xplane.pb"))
    assert bench_run.load_reader(name)(run) == pytest.approx(WANT[name])  # computed once a run
    assert set(got["unparted_ms"]) == {"attn", "experts"}  # the nameless copy; the bare ``sub``


@pytest.mark.parametrize("phase", list(PHASE_METRIC))
def test_parts_and_the_phases_share_of_unparted_are_the_phase_metric(bench_run, parts, tmp_path, phase):
    run = _run(tmp_path)
    whole = bench_run.load_reader(PHASE_METRIC[phase])(run)
    mine = [WANT[name] for name, (f, *_) in NEW.items() if f == phase]
    assert len(mine) == (2 if phase == "route" else 3)
    assert sum(mine) + parts.read(run)["unparted_ms"].get(phase, 0.0) == pytest.approx(whole, rel=1e-12)
    assert parts.unparted_ms(run) == pytest.approx(sum(parts.read(run)["unparted_ms"].values()))


@pytest.mark.parametrize("name", list(NEW))
def test_reader_on_a_trace_of_phases_without_parts(bench_run, tmp_path, name):
    """The parent of PR 38 under this benchmark. Before PR 36 no path named
    anything below a phase: nothing to read is no value, and no error. The
    parent itself names ``phase_kda_core``, which is a part by its form, so
    on Solar's cell it reads that one and counts the rest of the four phases
    as part-less; on the other two cells (no such layer) it reads nothing."""
    old = _run(tmp_path, "before-pr36")
    assert bench_run.load_reader(name)(old) is None
    assert bench_run.load_reader("step.attn_ms")(old) == pytest.approx(1.5 / 4)  # the phases it has, it gives
    parent = _run(tmp_path, "parent")
    got = bench_run.load_reader(name)(parent)
    if name == "step.kda_core_ms":
        assert got == pytest.approx(WANT[name])
    elif name == "step.unparted_ms":
            assert got == pytest.approx((1500 + 150 + 650 + 100) / 4 / 1e3)  # attn, route with the plan, experts, kda's rest
    else:
        assert got is None
    rehearsal = _run(tmp_path / "cpu")
    rehearsal.trace = None  # no device plane was reduced
    assert bench_run.load_reader(name)(rehearsal) is None
    no_steps = _run(tmp_path / "no-steps")
    no_steps.spans = []
    assert bench_run.load_reader(name)(no_steps) is None


# --------------------------------------------------- the entries ---


def test_new_entries_resolve_and_the_tiny_tree_still_builds(bench_run, tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("step.attn_in_ms")
    assert names[at:at + len(NEW)] == list(NEW) and at > names.index("kda.decay_mean")  # one run, after PR 36's
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (phase, part, layer, cells) in NEW.items():
        assert by_name[name] == {"name": name, "unit": "ms", "better": "lower", "source": "device_trace",
                                 "layer": layer, "moves": "train_items_per_s", "workloads": cells}
        with open(os.path.join(BENCH, "metrics", name + ".py")) as f:
            text = f.read()
        assert (f'parts.part_ms(run, "{phase}", "{part}")' if phase else "parts.unparted_ms(run)") in text
        assert phase is None or f"``phase_{phase}_{part}``" in text.replace("\n", " ")  # the docstring names its scope
    for phase, name in PHASE_METRIC.items():  # the phases' own metrics stay, on the same cells as their parts
        assert set(by_name[name]["workloads"]) == set(LM if phase != "kda" else LM[2:])
    # the benchmark's table of parts is the program's, by content and not by import
    from swiftsnails_tpu.utils.profiling import PARTS

    from lib import parts

    assert {(f, p) for f, p, _, _ in NEW.values() if f} == {(f, p) for f, ps in PARTS.items() for p in ps}
    assert set(parts.PARTED) == set(PARTS)
    tree = tiny_tree.build(str(tmp_path / "tree"))
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        tiny = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert tiny["step.kda_core_ms"]["workloads"] == ["tiny-solar.tiny-train-4k", "tiny-logreg.tiny-train-again"]
    assert all(os.path.isfile(os.path.join(tree, "benchmark", "metrics", n + ".py")) for n in NEW)
    assert os.path.isfile(os.path.join(tree, "benchmark", "lib", "parts.py"))


# --------------------------------------------------- the operator's tool ---


def test_the_operators_listing_reads_a_capture_through_the_same_reduction(bench_run, parts, tmp_path, capsys):
    """``tools/device_parts.py`` on the hand-made capture: ms a step by phase
    and part as the readers give them, each operation with its phase / part
    beside its XLA name."""
    run = _run(tmp_path)
    spec = importlib.util.spec_from_file_location("device_parts", os.path.join(ROOT, "tools", "device_parts.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    window = (1000, 1000 + 4 * 10**9)
    text = tool.report(run.extra["profile"].dir, steps=4, top=5, kinds=5, window=window)
    rows = {tuple(line.split()[:2]): float(line.split()[2]) for line in text.split("\n\n")[1].splitlines()[1:]}
    for name, (phase, part, _, _) in NEW.items():
        if phase:
            assert rows[(phase, part)] == pytest.approx(WANT[name], abs=5.01e-4), name  # printed to 0.001 ms
    assert rows[("attn", "-")] + rows[("experts", "-")] == pytest.approx(WANT["step.unparted_ms"], abs=1e-3)
    assert rows[("mlp", "-")] == pytest.approx(0.05) and rows[("opt", "-")] == pytest.approx(0.0425, abs=5.01e-4)
    longest = text.split("longest operations\n")[1].splitlines()
    assert longest[0].split()[:3] == ["attn", "core", "k.3"] and "tpu_custom_call" in longest[0]
    assert longest[1].split()[:3] == ["experts", "products", "k.10"]
    # as a command, over the whole capture (the late operation too), in ms
    assert tool.main([run.extra["profile"].dir, "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "1 device plane(s)" in out and "ms/step" not in out and re.search(r"attn +in +0\.800 ms", out)
