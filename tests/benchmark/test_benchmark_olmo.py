"""Olmo-Hybrid-7B's cell at a size a test run can hold: the tiny cell through
``run.execute`` on the CPU (the kernels in interpret mode), the control and
every fault the reference can plant judged at the tiny cell's limits (each
has to come out not correct), the configuration's file against the catalog
row's keys, its own ``published`` and ``keys``, the parameters and
operations by hand, and the entries this cell added to ``BENCHMARK.json``,
found by search."""

import importlib.util
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
import tiny_tree  # noqa: E402  (it finds this cell's tiny files by their ``tiny_of`` keys)

CELL = "olmo-hybrid-7b.train-4096"
TINY = "tiny-olmo.tiny-train-4096"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "olmo-hybrid-7b.json")
NEW = ["step.gdn_ms", "step.gdn_in_ms", "step.gdn_core_ms", "step.gdn_out_ms", "kernel.gdn_roofline",
       "gdn.decay_mean"]
REPORTED = ["train.input_wait_share", "train.step_mfu", "device.idle_share.train", "entry.build_trainer_s",
            "entry.data_load_s", "train.producer_busy_share", "train.h2d_share", "train.finalize_s",
            "train.drain_s", "step.device_ms", "step.unscoped_ms", "step.attn_ms", "step.attn_in_ms",
            "step.attn_out_ms", "step.attn_core_ms", "kernel.attn_roofline", "step.mlp_ms", "step.head_ms",
            "step.opt_ms"]
NOTHING_TO_READ = ["moe.held_share", "moe.load_max_over_mean", "moe.dropped", "step.route_ms", "step.experts_ms",
                   "kernel.experts_roofline", "step.route_score_ms", "step.route_plan_ms", "step.experts_gather_ms",
                   "step.experts_scatter_ms", "step.experts_products_ms", "step.kda_ms", "step.kda_in_ms",
                   "step.kda_out_ms", "step.kda_core_ms", "kernel.kda_roofline", "kda.decay_mean",
                   "step.unparted_ms", "kernel.train_roofline", "step.prep_ms", "step.noise_ms",
                   "diffusion.masked_share"]
# the tiny cell's own: bfloat16 operands at widths of 64, 16 and 32 move a loss
# by 8.4e-4, a leaf's gradient by 5.1e-2 and its change by 7.8e-3 (on the CPU,
# seed 2147483999); the control reads 0.60 on the change, the mildest fault
# (q/k normed a head) 1.7e-3 on the first loss and 1.5e-2 on the change
LIMITS = {"loss_step1": 1.2e-3, "loss_step2": 1.2e-3, "loss_step3": 1.2e-3, "grad1_worst_leaf": 0.08,
          "change3_worst_leaf": 0.012}
FAULTS = ["half_batch", "state_unchanged", "pre_norm", "qk_norm_per_head", "sigmoid_gate", "no_decay",
          "beta_not_doubled", "no_conv", "state_dropped"]
# the catalog row's ``config`` (guides/model-configs/architectures.jsonl, Olmo-Hybrid-7B)
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": PERIOD * 8, "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tree = tiny_tree.build(str(tmp_path_factory.mktemp("olmo") / "tree"))
    with open(os.path.join(tree, "benchmark", "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": LIMITS}, f)
    sys.path.insert(0, os.path.join(tree, "benchmark"))
    spec = importlib.util.spec_from_file_location("bench_run_olmo", os.path.join(tree, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def sound(bench, tmp_path_factory):
    """One traced rehearsal of the tiny cell: (run, result line)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    args = types.SimpleNamespace(workload=TINY, seed=2147483999, seconds=0.3, trace=1)
    work = str(tmp_path_factory.mktemp("work"))
    return bench.execute(args, bench.load_json(bench.ROOT, "BENCHMARK.json"), work)


def test_tiny_cell_runs_through_execute(sound):
    run, line = sound
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line["compared"]
    assert set(line["compared"]) == set(LIMITS)
    got = {k: v["value"] for k, v in line["metrics"].items()}  # a rehearsal prints counts alone
    assert set(got) == {"entry.compiles_in_window", "gdn.decay_mean"}  # no moe.*: there are no experts
    assert got["entry.compiles_in_window"] == 0.0 and 0.5 < got["gdn.decay_mean"] < 1.0
    assert "moe" not in run.counters and "kda" not in run.counters
    leaves = run.counters["readings"]["program"]["change"]
    assert {"gdn.wa", "gdn.wg", "gdn.conv_v", "gdn.a_log", "gdn.dt_bias", "gqa.q_norm", "dense.mlp_up"} <= set(leaves)
    assert "router_bias" not in leaves and not any(k.startswith(("moe.", "kda.")) for k in leaves)
    assert run.counters["items"] == run.counters["steps"] * 2 * 128  # L tokens with a target a row


@pytest.mark.parametrize("part", ["control"] + FAULTS)
def test_control_and_faults_are_not_correct(bench, sound, part):
    """The reference in the control's precision, or with a fault planted,
    put in the program's place and judged at the cell's limits."""
    from lib import compare

    run, _ = sound
    adapter, batches = run.extra["adapter"], run.extra["batches"]
    reference = run.counters["readings"]["reference"]
    other = adapter.reference(batches, **({"precision": "bfloat16"} if part == "control" else {"fault": part}))
    numbers = compare.train_numbers(reference, other)
    numbers.pop("worst_leaves")
    ok, compared = compare.judge({**adapter.extra_numbers(batches), **numbers}, run.limits)
    failed = [k for k, c in compared.items() if not c["value"] <= c["limit"]]
    assert not ok and failed, compared
    assert part == "control" or part in set(adapter.parts()) | {"half_batch"}  # control.py reads every one


def test_weights_come_from_init_seed_under_each_leafs_law(sound):
    import dataclasses

    import numpy as np

    run, _ = sound
    adapter = run.extra["adapter"]
    assert adapter.weights_seed == run.config["init"]["seed"]
    same = type(adapter)(dataclasses.replace(run, seed=run.seed + 1), adapter.trainer)
    mine, theirs = adapter._weights(), same._weights()
    assert all(np.array_equal(mine[k], theirs[k]) for k in mine)
    model = run.model
    assert [model.law_of(k) for k in ("gdn.a_log", "gdn.dt_bias", "gdn.conv_v", "gdn.o_norm", "gqa.q_norm",
                                      "gdn.wa", "dense.mlp_norm")] == [
        "a_log", "dt_bias", "taps", "ones", "ones", "normal", "ones"]
    a = np.exp(np.asarray(mine["gdn.a_log"]))
    rate = np.log1p(np.exp(np.asarray(mine["gdn.dt_bias"])))
    assert 1.0 <= a.min() and a.max() <= 16.0 and 0.99e-3 <= rate.min() and rate.max() <= 0.101
    with open(CONFIG) as f:
        assert json.load(f)["init"] == {"std": 0.02, "seed": 42}


def test_configuration_keeps_the_published_widths():
    with open(CONFIG) as f:
        config = json.load(f)
    keys, published = config["keys"], config["published"]
    assert config["reduced"] == ["num_hidden_layers", "num_attention_heads", "num_key_value_heads",
                                 "linear_num_key_heads", "linear_num_value_heads", "layer_types", "vocab_size"]
    assert published == {k: PUBLISHED[k] for k in config["reduced"]}
    for k, v in PUBLISHED.items():  # every key of the catalog row, changed only where 'reduced' says
        assert k in config, k
        assert (config[k] == v) != (k in config["reduced"]), k
    for k in ("hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
              "layer_types", "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim", "rms_norm_eps", "vocab_size"):
        assert keys[k] == config[k], k  # the program runs what the file states
    assert keys["linear_allow_neg_eigval"] == 1 and config["linear_allow_neg_eigval"] is True
    assert (keys["head_dim"], keys["use_rope"], keys["qk_norm"], keys["norm_order"],
            keys["use_gqa_gate"]) == (128, 0, "width", "post", 0)
    assert keys["head_dim"] * PUBLISHED["num_attention_heads"] == PUBLISHED["hidden_size"]  # the config states none
    assert keys["first_k_dense_replace"] == keys["num_hidden_layers"] == 4 and keys["layer_types"] == PERIOD
    assert keys["num_attention_heads"] * 2 == 30 and keys["linear_num_value_heads"] * 2 == 30  # a two-way split
    assert keys["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert (keys["seq_len"], keys["batch_size"], keys["learning_rate"]) == (4096, 1, 6e-7)
    assert {"softmax_layer", "residual_order", "gdn_shapes", "gdn_init", "init", "optimizer_values", "feed"} <= set(
        config["assumed"])
    assert config["departures"] and config["guarantees"] and config["precision"] and config["control"]
    for words in ("two chips", "eight ways", "twice its share", "766,241,946", "12.26 GB"):
        assert words in config["deployment"], words
    assert "7,430,870,688" in config["source_detail"] and "766,241,946" in config["source_detail"]
    with open(os.path.join(ROOT, "benchmark", "traffic", "train-4096.json")) as f:
        mix = json.load(f)
    assert mix["job"] == "train" and mix["keys"]["seq_len"] == keys["seq_len"] and "13.12 GB" in mix["why"]
    assert config["feed"]["tokens"] == 256 * (keys["seq_len"] + 1)


def test_operations_and_parameters_by_hand(bench):
    from lib import jobs

    model = jobs.load_model("olmo_hybrid")
    with open(CONFIG) as f:
        config = json.load(f)
    keys = config["keys"]
    d, ffn = 3840, 3 * 3840 * 11008
    delta = 2 * d * 1440 + 3 * d * 2880 + 2 * d * 15 + 4 * (1440 + 1440 + 2880) + 15 + 15 + 192 + 2 * d + ffn
    softmax = 4 * d * 1920 + 2 * 1920 + 2 * d + ffn
    assert (delta, softmax) == (171_195_102, 156_314_880)
    held = 3 * delta + softmax + 2 * 12544 * d + d
    assert model.parameters_held(keys) == held == 766_241_946  # 12.26 GB at 16 B
    whole = {**keys, **{k: config["published"][k] for k in config["reduced"]}}
    assert model.parameters_held(whole) == 7_430_870_688  # the 7B of its name
    all_heads = {**keys, "num_attention_heads": 30, "num_key_value_heads": 30, "linear_num_value_heads": 30}
    assert model.parameters_held(all_heads) == 928_862_196  # 14.86 GB: why the heads are shared out
    matrices = 3 * (delta - 4 * 5760 - 30 - 192 - 2 * d) + (softmax - 2 * 1920 - 2 * d) + d * 12544
    assert model.matrix_parameters_per_token(keys) == pytest.approx(matrices)
    assert model.attention_flops_per_token(keys) == pytest.approx(2 * 15 * 256 * 4097 / 2)
    recurrence = 2 * 3 * 15 * (3 * 96 * 192 + 64 * (3 * 96 + 2 * 192))
    assert model.gdn_flops_per_token(keys) == recurrence
    assert model.flops_per_item(keys) == pytest.approx(6 * matrices + 3 * (2 * 15 * 256 * 4097 / 2 + recurrence))
    assert model.flops_per_item(keys) * 4096 == pytest.approx(17.95e12, rel=1e-3)  # a step, no rematerialisation
    assert 6 * 4 * ffn * 4096 / (model.flops_per_item(keys) * 4096) == pytest.approx(0.694, abs=1e-3)
    from swiftsnails_tpu.ops.flash_attention import attention_flops
    from swiftsnails_tpu.ops.gated_delta import gated_delta_flops

    mine = gated_delta_flops(4096, 15, 96, 192, chunk=64)  # the program's count, the benchmark's restated
    assert model.gdn_kernel_flops_per_step(keys) == pytest.approx(3 * (2 * mine["fwd"] + mine["bwd"]))
    causal = attention_flops(4096, 15, 128, 128)
    assert model.attention_kernel_flops_per_step(keys) == pytest.approx(
        2 * causal["fwd"] + causal["dq"] + causal["dkv"])


def test_entries_are_appended_and_resolve(bench):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        record = json.load(f)
    names = [m["name"] for m in record["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW and at > names.index("sparse.indexer_kl")  # one run, after the sparse-attention cell's
    by_name = {m["name"]: m for m in record["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_items_per_s"
        assert m["source"] == ("program_counter" if name == "gdn.decay_mean" else "device_trace")
        assert m["layer"] == ("kernels" if name in ("step.gdn_ms", "step.gdn_core_ms", "kernel.gdn_roofline")
                              else "model step")
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    assert by_name["kernel.gdn_roofline"]["unit"] == "%" and by_name["gdn.decay_mean"]["unit"] == "ratio"
    for name in REPORTED:
        assert CELL in by_name[name]["workloads"], name
    for name in NOTHING_TO_READ:
        assert CELL not in by_name[name]["workloads"], name
    rate = next(m for m in record["end_to_end"] if m["name"] == "train_items_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    cell = next(w for w in record["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "olmo-hybrid-7b", "traffic": "train-4096", "chips": 1,
                    "why": cell["why"]} and len(cell["why"]) <= 200
    entry = next(c for c in record["configs"] if c["name"] == "olmo-hybrid-7b")
    with open(CONFIG) as f:
        config = json.load(f)
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/olmo-hybrid-7b.json" and len(entry["why"]) <= 200
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "limits", CELL + ".json"))
    # a run of another program, or none of the spans and counters: no value, no error
    from lib import jobs

    for model in ("widedeep", "solar", "olmo_hybrid"):
        empty = jobs.Run(config={"model": model, "keys": config["keys"]}, mix={}, seed=1, seconds=1.0, traced=True)
        for name in NEW:
            assert bench.load_reader(name)(empty) is None, (name, model)


def test_the_program_builds_the_stack_the_parent_refused():
    """The program before this cell reads no ``layer_types``: every layer is
    then grouped-query attention, and ``first_k_dense_replace`` 4 of 4 leaves
    no mixture layer, which it refused. The program now builds the stack."""
    from swiftsnails_tpu.models.moelm import MoELMTrainer
    from swiftsnails_tpu.utils.config import Config

    with open(CONFIG) as f:
        keys = json.load(f)["keys"]
    cfg = Config()
    for k, v in {**keys, "hidden_size": 64, "head_dim": 16, "intermediate_size": 32, "vocab_size": 64,
                 "linear_key_head_dim": 16, "linear_value_head_dim": 32, "seq_len": 64}.items():
        cfg.set(k, str(v))
    import numpy as np

    tr = MoELMTrainer(cfg, corpus_ids=np.zeros(1000, np.int32), vocab_size=64)
    assert tr.mixers == ("gdn", "gdn", "gdn", "gqa") and tr.n_moe == 0
