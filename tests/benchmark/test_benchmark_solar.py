"""Solar-Open2-250B's cell at a size a test run can hold: the tiny cell
through ``run.execute`` on the CPU (the kernels in interpret mode), the
control and every fault the reference can plant judged at the tiny cell's
limits (each has to come out not correct), the configuration's file against
the catalog row's keys, its own ``published`` and ``keys``, the parameters
and operations by hand, and the entries this cell added to ``BENCHMARK.json``."""

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

CELL = "solar-open2-250b.train-4k"
TINY = "tiny-solar.tiny-train-4k"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "solar-open2-250b.json")
PR36 = ["step.kda_ms", "kernel.kda_roofline", "kda.decay_mean"]
REPORTED = ["train.input_wait_share", "train.step_mfu", "device.idle_share.train", "entry.build_trainer_s",
            "entry.data_load_s", "train.producer_busy_share", "train.h2d_share", "train.finalize_s",
            "train.drain_s", "step.device_ms", "step.unscoped_ms", "step.attn_ms", "step.mlp_ms",
            "step.route_ms", "step.experts_ms", "step.head_ms", "step.opt_ms", "kernel.attn_roofline",
            "kernel.experts_roofline", "moe.held_share", "moe.load_max_over_mean", "moe.dropped"]
# the tiny cell's own: bfloat16 operands at widths of 64 and 16 move a loss by
# 6.2e-3, a leaf's gradient by 2.3e-2 and its change by 1.2e-2 (my CPU run,
# PR 36); the control reads 5.4e-2 on the gradient and 0.31 on the change, the
# mildest fault (beta not doubled) 0.21 on the gradient
LIMITS = {"loss_step1": 1.2e-2, "loss_step2": 1.2e-2, "loss_step3": 1.2e-2, "grad1_worst_leaf": 0.04,
          "change3_worst_leaf": 0.03}
FAULTS = ["half_batch", "state_unchanged", "no_decay", "beta_not_doubled", "no_conv", "no_kda_gate",
          "no_gqa_gate", "state_dropped"]
# the catalog row's ``config`` (guides/model-configs/architectures.jsonl, Solar-Open2-250B)
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "vocab_size": 196608, "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 320, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tree = tiny_tree.build(str(tmp_path_factory.mktemp("solar") / "tree"))
    with open(os.path.join(tree, "benchmark", "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": LIMITS}, f)
    sys.path.insert(0, os.path.join(tree, "benchmark"))
    spec = importlib.util.spec_from_file_location(
        "bench_run_solar", os.path.join(tree, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return str(tmp_path_factory.mktemp("work"))


@pytest.fixture(scope="module")
def sound(bench, work):
    """One traced rehearsal of the tiny cell: (run, result line)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    args = types.SimpleNamespace(workload=TINY, seed=2147483999, seconds=0.3, trace=1)
    return bench.execute(args, bench.load_json(bench.ROOT, "BENCHMARK.json"), work)


def test_tiny_cell_runs_through_execute(sound):
    run, line = sound
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, line["compared"]
    assert set(line["compared"]) == set(LIMITS)
    got = {k: v["value"] for k, v in line["metrics"].items()}  # a rehearsal prints counts alone
    assert set(got) == {"entry.compiles_in_window", "moe.held_share", "moe.load_max_over_mean",
                        "moe.dropped", "kda.decay_mean"}
    assert got["moe.dropped"] == 0.0 and got["entry.compiles_in_window"] == 0.0
    assert 15.0 < got["moe.held_share"] < 35.0  # 4 of 16 experts held: 25% give or take the skew
    assert 0.5 < got["kda.decay_mean"] < 1.0
    leaves = run.counters["readings"]["program"]["change"]
    assert {"gqa.wz", "kda.conv_q", "kda.a_log", "kda.dt_bias", "moe.shared_up", "router_bias"} <= set(leaves)
    assert "gqa.q_norm" not in leaves and not any(k.startswith("dense.") for k in leaves)
    assert run.counters["items"] == run.counters["steps"] * 2 * 128  # L tokens with a target a row


def test_the_window_is_counted_by_running_its_steps_again(sound, work):
    from swiftsnails_tpu.utils.flags import parse_role_argv

    run, _ = sound
    # tests/conftest.py empties the program's one config between tests; a run has it throughout
    parse_role_argv(["-config", os.path.join(work, "job.conf")])
    counts = run.model.window_counts(run)
    steps, layers, experts = counts.shape
    assert steps == run.counters["steps"] > 0 and (layers, experts) == (4, 16)
    assert (counts.sum(axis=-1) == 2 * 128 * 3).all()  # every token chose three


@pytest.mark.parametrize("part", ["control"] + FAULTS)
def test_control_and_faults_are_not_correct(bench, sound, part):
    """The reference in the control's precision, or with a fault planted,
    put in the program's place and judged at the cell's limits."""
    from lib import compare

    run, _ = sound
    adapter, batches = run.extra["adapter"], run.extra["batches"]
    reference = run.counters["readings"]["reference"]
    other = adapter.reference(batches, **({"precision": "bfloat16"} if part == "control"
                                          else {"fault": part}))
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
    assert [model.law_of(k) for k in ("kda.a_log", "kda.dt_bias", "kda.conv_v", "kda.o_norm", "kda.wq",
                                      "final_norm")] == ["a_log", "dt_bias", "taps", "ones", "normal", "ones"]
    a = np.exp(np.asarray(mine["kda.a_log"]))
    rate = np.log1p(np.exp(np.asarray(mine["kda.dt_bias"])))
    assert 1.0 <= a.min() and a.max() <= 16.0 and 0.99e-3 <= rate.min() and rate.max() <= 0.101
    assert np.abs(np.asarray(mine["kda.conv_q"])).max() <= 0.5 and float(np.asarray(mine["kda.o_norm"]).min()) == 1.0
    with open(CONFIG) as f:
        assert json.load(f)["init"] == {"std": 0.02, "seed": 36}


def test_configuration_keeps_the_published_widths():
    with open(CONFIG) as f:
        config = json.load(f)
    keys, published = config["keys"], config["published"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size", "num_attention_heads",
                                 "num_key_value_heads", "linear_attn_config"]
    assert published == {k: PUBLISHED[k] for k in config["reduced"]}
    for k, v in PUBLISHED.items():  # every key of the catalog row, changed only where 'reduced' says
        assert k in config, k
        assert (config[k] == v) != (k in config["reduced"]), k
    group = config["linear_attn_config"]  # of the nested group the count of heads alone
    assert group == {**PUBLISHED["linear_attn_config"], "num_heads": 8}
    assert (keys["hidden_size"], keys["head_dim"], keys["moe_intermediate_size"], keys["num_experts_per_tok"],
            keys["rms_norm_eps"], keys["linear_attn_config.head_dim"],
            keys["linear_attn_config.short_conv_kernel_size"]) == (4096, 128, 1280, 8, 1e-5, 128, 4)
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
              "moe_intermediate_size", "num_experts_per_tok", "num_hidden_layers", "vocab_size",
              "n_shared_experts", "routed_scaling_factor", "first_k_dense_replace", "gqa_layers"):
        assert keys[k] == config[k], k  # the program runs what the file states
    assert keys["linear_attn_config.num_heads"] == group["num_heads"] == keys["num_attention_heads"] == 8
    assert (keys["use_rope"], keys["use_gqa_gate"], keys["kda_allow_neg_eigval"], keys["qk_norm"]) == (0, 1, 1, 0)
    assert keys["router_experts"] == published["n_routed_experts"] == 320
    assert keys["experts_held"] == config["n_routed_experts"] == 8 == published["n_routed_experts"] // 40
    assert keys["vocab_size"] * 8 == published["vocab_size"] and keys["num_attention_heads"] * 8 == 64
    assert keys["num_hidden_layers"] == 4 and keys["gqa_layers"][:2] == [0, 4]  # one whole period
    assert (keys["seq_len"], keys["batch_size"]) == (2048, 1)
    assert {"kda_shapes", "kda_init", "softmax_layer", "router", "init", "optimizer_values", "feed"} <= set(
        config["assumed"])
    assert config["departures"] and config["guarantees"] and config["precision"] and config["control"]
    assert "forty chips" in config["deployment"] and "840,871,320" in config["deployment"]
    with open(os.path.join(ROOT, "benchmark", "traffic", "train-4k.json")) as f:
        mix = json.load(f)
    assert mix["job"] == "train" and mix["keys"]["seq_len"] == keys["seq_len"] and "16.45 GB" in mix["why"]
    assert config["feed"]["tokens"] == 256 * (keys["seq_len"] + 1)


def test_operations_and_parameters_by_hand(bench):
    from lib import jobs

    model = jobs.load_model("solar")
    with open(CONFIG) as f:
        keys = json.load(f)["keys"]
    softmax = 3 * 4096 * 1024 + 2 * 4096 * 128
    assert softmax + 2 * 4096 == 13_639_680
    delta = 4 * 4096 * 1024 + 3 * 4 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 8 + 1024 + 4096 * 8 + 128
    assert delta + 2 * 4096 == 18_142_344
    expert = 3 * 4096 * 1280
    forward = 4096 * 320 + expert + 8 * expert
    assert expert == 15_728_640 and 4 * forward == 571_473_920
    held = softmax + 3 * delta + 4 * 2 * 4096 + 4 * forward + 2 * 24576 * 4096 + 4096
    assert model.parameters_held(keys) == held == 840_871_320
    # the matrices a token multiplies by: the convolutions' taps, A, dt_bias and the gains are none
    matrices = softmax + 3 * (delta - 3 * 4 * 1024 - 8 - 1024 - 128) + 4 * (4096 * 320 + expert + 8 * 8 / 320 * expert) \
        + 4096 * 24576
    assert model.matrix_parameters_per_token(keys) == pytest.approx(matrices)
    assert model.attention_flops_per_token(keys) == pytest.approx(2 * 8 * 256 * 2049 / 2)
    recurrence = 2 * 3 * 8 * (3 * 128 * 128 + 64 * 5 * 128)
    assert model.kda_flops_per_token(keys) == recurrence
    assert model.flops_per_item(keys) == pytest.approx(6 * matrices + 3 * (2 * 8 * 256 * 2049 / 2 + recurrence))
    assert model.flops_per_item(keys) * 2048 == pytest.approx(3.13e12, rel=1e-2)  # a step, no rematerialisation
    assert model.kda_kernel_flops_per_step(keys) == 2048 * recurrence * 5  # forward twice, the backward's three
    from swiftsnails_tpu.ops.flash_attention import attention_flops
    from swiftsnails_tpu.ops.gated_delta import gated_delta_flops

    mine = gated_delta_flops(2048, 8, 128, 128, chunk=64)  # the program's count, the benchmark's restated
    assert model.kda_kernel_flops_per_step(keys) == 3 * (2 * mine["fwd"] + mine["bwd"])
    causal = attention_flops(2048, 8, 128, 128)
    assert model.attention_kernel_flops_per_step(keys) == pytest.approx(
        2 * causal["fwd"] + causal["dq"] + causal["dkv"])
    assert model.experts_kernel_flops(keys, 1000.0) == pytest.approx(1000 * 3 * 2 * 4096 * 1280 * 4)


def test_entries_are_appended_and_resolve(bench):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        record = json.load(f)
    names = [m["name"] for m in record["per_layer"]]
    at = names.index(PR36[0])
    assert names[at:at + len(PR36)] == PR36 and at > names.index("diffusion.masked_share")
    by_name = {m["name"]: m for m in record["per_layer"]}
    for name, source, layer in zip(PR36, ("device_trace", "device_trace", "program_counter"),
                                   ("kernels", "kernels", "model step")):
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_items_per_s"
        assert (m["source"], m["layer"]) == (source, layer)
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    assert by_name["kernel.kda_roofline"]["unit"] == "%"
    for name in REPORTED:
        assert CELL in by_name[name]["workloads"], name
    for name in ("kernel.train_roofline", "step.prep_ms", "step.noise_ms", "diffusion.masked_share"):
        assert CELL not in by_name[name]["workloads"]
    rate = next(m for m in record["end_to_end"] if m["name"] == "train_items_per_s")
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    cell = next(w for w in record["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "solar-open2-250b", "traffic": "train-4k", "chips": 1,
                    "why": cell["why"]} and len(cell["why"]) <= 200
    assert all(w["chips"] == 1 for w in record["workloads"])
    entry = next(c for c in record["configs"] if c["name"] == "solar-open2-250b")
    with open(CONFIG) as f:
        config = json.load(f)
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/solar-open2-250b.json" and len(entry["why"]) <= 200
    # a run of another program, or none of the spans and counters: no value, no error
    from lib import jobs

    for model in ("widedeep", "moonlight", "solar"):
        empty = jobs.Run(config={"model": model, "keys": {}}, mix={}, seed=1, seconds=1.0, traced=True)
        for name in PR36:
            assert bench.load_reader(name)(empty) is None, (name, model)
