"""The second level of names on the device timeline (``utils/profiling.py``
``PARTS`` / ``part_scope``), on the CPU, from the lowered step's ``op_name``s:
every matrix product and every custom call of a phase that has parts names
one, in the forward pass, the rematerialised forward and the backward; the
kernels stand under the part that is theirs; an unknown pair raises; and the
parts are metadata (without them the lowered module is the same text).

The steps are lowered for a TPU (no chip needed, nothing is compiled), so
the kernels are Mosaic calls and not their interpreted bodies (the delta
rule's two stand under ``phase_kda_core`` in all three passes, or under
``phase_gdn_core`` where Gated DeltaNet calls its per-head pair); five tiny models: latent
attention over a dense and two mixture layers (Moonlight's kind), grouped
queries under the block-diffusion mask (SDAR's), the layer table with
delta-rule layers and a gated softmax layer (Solar's), grouped queries under
a learned selection (Keye's, at tiles of 256: a tile's words are then a block
Mosaic tiles), and a dense stack of Gated DeltaNet layers and a softmax layer
with OLMo 2's residual order (Olmo-Hybrid's)."""

import contextlib
import re
import types

import pytest

import jax
import jax.numpy as jnp

import test_moelm
import test_moelm_diffusion
import test_moelm_gdn
import test_moelm_kda
import test_moelm_sparse
from swiftsnails_tpu.models import moelm
from swiftsnails_tpu.ops import flash_attention, gated_delta, grouped_matmul, sparse_attention
from swiftsnails_tpu.utils.profiling import PARTS, PHASES, part_scope

_TILES_256 = {"seq_len": 512, "sa_config.q_chunk_size": 256, "sa_config.kv_chunk_size": 256}
MODELS = {"mla": test_moelm, "gqa_bd": test_moelm_diffusion, "kda": test_moelm_kda,
          "sparse": types.SimpleNamespace(_trainer=lambda **o: test_moelm_sparse._trainer(**{**_TILES_256, **o})),
          "gdn": test_moelm_gdn}
DELTA = {"kda": "kda", "gdn": "gdn"}  # the models whose delta rule stands under ``phase_<it>_core``
DELTA_KERNELS = {"kda": "gated_delta_", "gdn": "gated_delta_head_"}  # the kernels' names before ``fwd`` / ``bwd``
PASSES = ("forward", "rematerialised", "backward")
_TOKEN = re.compile(r"phase_([a-z]+)(?:_([a-z]+))?")


def _lowered(kind, mp, debug_info, tpu=True):
    """The tiny model's ``train_step`` with bfloat16 operands, as lowered for
    a TPU (the kernels Mosaic calls) or for the CPU (their interpreted bodies)."""
    for mod in (flash_attention, gated_delta, grouped_matmul, sparse_attention):
        mp.setattr(mod, "on_tpu", lambda: tpu)
    tr, _ = MODELS[kind]._trainer(matmul_dtype="bfloat16")
    state = jax.eval_shape(tr.init_state)
    batch = {k: jnp.asarray(v) for k, v in next(iter(tr.batches())).items()}
    traced = jax.jit(tr.train_step).trace(state, batch, jax.random.PRNGKey(0))
    return traced.lower(lowering_platforms=("tpu" if tpu else "cpu",)).as_text(debug_info=debug_info)


def _op_paths(txt):
    """[(operation, name-scope path from the entry function)] of every
    ``dot_general`` and ``custom_call``, through the private functions that
    inner jits and scans lower to; a Mosaic call is ``tpu_custom_call``."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', txt, re.M))
    func, calls, found = None, [], []
    for line in txt.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@([\w.\-]+)", line)
        if m:
            func = m.group(1)
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        path = locs.get(ref.group(1), "") if ref else ""
        m = re.search(r"(?:func\.)?call @([\w.\-]+)\(", line)
        if m:
            calls.append((func, m.group(1), path))
        m = re.search(r"stablehlo\.(dot_general|custom_call)\b(?: @([\w.]+))?", line)
        if m:
            found.append((func, m.group(2) or m.group(1), path))

    def prefixes(f, seen=()):
        sites = [(c, p) for c, callee, p in calls if callee == f and c not in seen]
        if not sites:
            return [""]
        return [pre + "/" + p for c, p in sites for pre in prefixes(c, seen + (f,))]

    return [(op, pre + "/" + p) for f, op, p in found for pre in prefixes(f)]


def _pass_of(path):
    if "rematted_computation" in path:
        return "rematerialised"
    return "backward" if "transpose(" in path else "forward"


@pytest.fixture(scope="module", params=list(MODELS))
def lowered(request):
    with pytest.MonkeyPatch.context() as mp:
        txt = _lowered(request.param, mp, debug_info=True)
    return request.param, _op_paths(txt), txt


@pytest.mark.parametrize("which", PASSES)
def test_every_product_and_call_of_a_parted_phase_names_its_part(lowered, which):
    kind, ops, _ = lowered
    seen = set()
    for op, path in ops:
        tokens = _TOKEN.findall(path)
        if _pass_of(path) != which or not tokens or tokens[-1][0] not in PARTS:
            continue
        phase, part = tokens[-1]
        assert part in PARTS[phase], f"{op} under {path} names no part of {phase}"
        # the part stands inside its phase's scope (the plan inside ``phase_experts``, where it is made)
        assert tokens[-2] == ("experts" if (phase, part) == ("route", "plan") else phase, ""), path
        seen.add((phase, part))
    want = {("attn", "in"), ("attn", "core"), ("attn", "out")}
    if kind != "gdn":  # a dense stack has no mixture layer
        want |= {("route", "score"), ("experts", "products")}
    if kind in DELTA:
        want |= {(DELTA[kind], "in"), (DELTA[kind], "core"), (DELTA[kind], "out")}
    if kind == "sparse":  # the selection's products stand in a loop's body, which this walk does not enter
        want |= {("attn", "index")}
    assert want <= seen, (kind, which, sorted(want - seen))
    # the moves and the plan hold no product; their kernel-made buffers are the gather's and the scatter's
    extras = {("experts", "gather"), ("experts", "scatter"), ("route", "plan")}
    if kind == "sparse":
        extras |= {("attn", "select")}
    assert seen - want <= extras


@pytest.mark.parametrize("family", ["flash_attention", "grouped_matmul", "gated_delta"])
def test_kernels_stand_under_the_part_that_is_theirs(lowered, family):
    kind, ops, _ = lowered
    calls = [path for op, path in ops if op == "tpu_custom_call" and family in path]
    if family == "gated_delta":  # the delta rule's two kernels, in every pass, under its mixer's ``core``
        if kind not in DELTA:
            assert not calls, calls
            return
        form = DELTA_KERNELS[kind]  # a decay a channel (KDA) or a head (Gated DeltaNet): the form's own pair
        names = {re.search(r"gated_delta_\w+", p).group(0) for p in calls}
        assert names == {form + "fwd", form + "bwd"}
        assert all(_TOKEN.findall(p)[-1] == (DELTA[kind], "core") for p in calls), calls
        assert {_pass_of(p) for p in calls if form + "fwd" in p} == {"forward", "rematerialised"}
        assert {_pass_of(p) for p in calls if form + "bwd" in p} == {"backward"}
        return
    if family == "flash_attention":
        names = {re.search(r"flash_attention_\w+", p).group(0) for p in calls}
        assert names == {"flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv"}
        assert all(_TOKEN.findall(p)[-1] == ("attn", "core") for p in calls), calls
        assert {_pass_of(p) for p in calls} == set(PASSES)
        return
    if kind == "gdn":  # no mixture layer, no grouped products
        assert not calls, calls
        return
    names = {re.search(r"grouped_matmul\w*", p).group(0) for p in calls}
    assert names == {"grouped_matmul", "grouped_matmul_swiglu", "grouped_matmul_dswiglu", "grouped_matmul_dx",
                     "grouped_matmul_dw", "grouped_matmul_unfilled_rows"}
    for p in calls:
        part = _TOKEN.findall(p)[-1]
        if "grouped_matmul_unfilled_rows" in p:  # writes nothing: the buffer a move's loop starts from
            assert part in (("experts", "gather"), ("experts", "scatter")), p
        else:
            assert part == ("experts", "products"), p


def test_the_plan_stands_inside_the_experts_phase_and_reads_as_route(lowered):
    """``plan_rows`` is called from ``_experts``: its operations' last token
    is ``phase_route_plan``, the one before it ``phase_experts``."""
    kind, _, txt = lowered
    plan = set(re.findall(r'loc\("([^"]*phase_route_plan[^"]*)"', txt))
    if kind == "gdn":  # a dense stack plans nothing
        assert not plan and "phase_experts" not in txt
        return
    assert any(re.search(r"phase_route_plan/.*sort", p) for p in plan), sorted(plan)[:5]
    assert all(_TOKEN.findall(p)[-2:] == [("experts", ""), ("route", "plan")] for p in plan)
    assert not re.search(r'loc\("[^"]*phase_experts[^"]*phase_route/', txt)  # the bare scope stood there before


@pytest.mark.parametrize("kind", list(MODELS))
def test_parts_are_metadata(kind):
    """With ``part_scope`` a null context the lowered module, locations and
    metadata left out, is the same text: no operation, no operand. For the
    CPU that is the whole step with the kernels' bodies; for a TPU a Mosaic
    call's serialized body carries its own locations, name scopes among them,
    and is left out of the comparison."""
    body = re.compile(r'\\22body\\22: \\22[^\\]*\\22')
    with pytest.MonkeyPatch.context() as mp:
        with_parts = {tpu: _lowered(kind, mp, debug_info=False, tpu=tpu) for tpu in (False, True)}
        null = lambda phase, part: contextlib.nullcontext()  # noqa: E731
        mp.setattr(moelm, "part_scope", null)
        mp.setattr(gated_delta, "part_scope", null)
        without = _lowered(kind, mp, debug_info=True)
        assert "phase_attn" in without and not re.search(r"phase_[a-z]+_[a-z]+", without)
        assert _lowered(kind, mp, debug_info=False, tpu=False) == with_parts[False]
        assert body.sub("", _lowered(kind, mp, debug_info=False)) == body.sub("", with_parts[True])
    assert "loc(" not in with_parts[False] and "phase_" not in with_parts[False]
    # the Mosaic calls of two mixture layers, three passes; of the dense stack's three delta-rule layers and one
    # softmax layer
    assert len(body.findall(with_parts[True])) >= (13 if kind == "gdn" else 20)


@pytest.mark.parametrize("pair", [("attn", "plan"), ("mlp", "in"), ("warmup", "core"), ("kda", "products"),
                                  ("route", ""), ("opt", "core")])
def test_part_scope_raises_on_an_unknown_pair(pair):
    with pytest.raises(ValueError, match="unknown part"):
        part_scope(*pair)


def test_the_table_of_parts():
    assert PARTS == {"attn": ("in", "core", "out", "index", "select"), "kda": ("in", "core", "out"),
                     "gdn": ("in", "core", "out"), "experts": ("gather", "products", "scatter"),
                     "route": ("score", "plan")}
    assert set(PARTS) <= set(PHASES)
    # benchmark/lib/parts.py reads phase_[a-z]+_[a-z]+, benchmark/lib/scopes.py the phase before the second _
    assert all(p.isalpha() and p.islower() for parts in PARTS.values() for p in parts)
    assert gated_delta.CORE_SCOPE == ("kda", "core")  # ``phase_kda_core``: kernel.kda_roofline reads that string
    with part_scope(*gated_delta.CORE_SCOPE), jax.named_scope("x"):
        pass
    from swiftsnails_tpu.telemetry.audit import _SCOPE_RE

    for phase, parts in PARTS.items():
        for part in parts:
            assert not _SCOPE_RE.search(f"jit(_step)/phase_{phase}/phase_{phase}_{part}/mul")
