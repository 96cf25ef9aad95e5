"""SDAR-30B-A3B-Chat's block-diffusion cell at a size a test run can hold: the
tiny cell through ``run.execute`` on the CPU (the kernels in interpret mode),
the control and every fault the reference can plant judged at the tiny cell's
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

CELL = "sdar-30b-a3b.train-bd-4k"
TINY = "tiny-sdar.tiny-train-bd-4k"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "sdar-30b-a3b.json")
PR34 = ["step.noise_ms", "diffusion.masked_share"]
REPORTED = ["train.input_wait_share", "train.step_mfu", "device.idle_share.train", "entry.build_trainer_s",
            "entry.data_load_s", "train.producer_busy_share", "train.h2d_share", "train.finalize_s",
            "train.drain_s", "step.device_ms", "step.unscoped_ms", "step.attn_ms", "step.route_ms",
            "step.experts_ms", "step.head_ms", "step.opt_ms", "kernel.attn_roofline",
            "kernel.experts_roofline", "moe.held_share", "moe.load_max_over_mean", "moe.dropped"]
# the tiny cell's own: bfloat16 operands at widths of 64 and 8 move a loss by
# 1.2e-3, a leaf's gradient by 1.6e-2 and its change by 1.2e-2 at most over
# four seeds (my CPU run, PR 34); the control reads 0.25 on the change (a
# stored bfloat16 weight hardly takes a step), the mildest fault 0.046 on the
# gradient (fifteen experts) or 0.017 on the first loss (the causal block)
LIMITS = {"loss_step1": 3e-3, "loss_step2": 3e-3, "loss_step3": 3e-3, "grad1_worst_leaf": 0.03,
          "change3_worst_leaf": 0.025}
FAULTS = ["half_batch", "state_unchanged", "causal_noised", "noised_past", "no_p_weight",
          "no_qk_norm", "fifteen_experts", "busiest_expert_out"]
# the catalog row's ``config`` (guides/model-configs/architectures.jsonl, SDAR-30B-A3B-Chat)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tree = tiny_tree.build(str(tmp_path_factory.mktemp("sdar") / "tree"))
    with open(os.path.join(tree, "benchmark", "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": LIMITS}, f)
    sys.path.insert(0, os.path.join(tree, "benchmark"))
    spec = importlib.util.spec_from_file_location(
        "bench_run_sdar", os.path.join(tree, "benchmark", "run.py"))
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
                        "moe.dropped", "diffusion.masked_share"}
    assert got["moe.dropped"] == 0.0 and got["entry.compiles_in_window"] == 0.0
    assert 15.0 < got["moe.held_share"] < 35.0  # 4 of 16 experts held: 25% give or take the skew
    assert 1.0 <= got["moe.load_max_over_mean"] < 4.0
    assert 30.0 < got["diffusion.masked_share"] < 70.0  # 3 steps x 2 rows x 16 blocks, each with a t of its own
    leaves = run.counters["readings"]["program"]["change"]
    assert "moe.q_norm" in leaves and "moe.experts_down" in leaves and "moe.wk" in leaves
    assert "router_bias" not in leaves and not any(k.startswith("dense.") for k in leaves)
    assert run.counters["items"] == run.counters["steps"] * 2 * 64  # L clean tokens a row


def test_the_reference_is_handed_the_batchs_own_noise(sound):
    """The draw is part of the batch: what the probe kept of the warm steps
    carries it, and the masked share the program counted is the batches'."""
    import numpy as np

    run, _ = sound
    batches = run.extra["batches"]
    assert len(batches) == 3 and all(set(b) == {"tokens", "noised", "p_mask"} for b in batches)
    assert all(b["tokens"].shape == (2, 64) and b["p_mask"].shape == (2, 16) for b in batches)
    assert not (np.concatenate([b["tokens"] for b in batches]) == 255).any()  # the mask's id is never fed
    share = 100.0 * np.mean([b["noised"].mean() for b in batches])
    assert run.counters["diffusion"]["masked_share_pct"] == pytest.approx(share)


def test_the_window_is_counted_by_running_its_steps_again(sound, work):
    from swiftsnails_tpu.utils.flags import parse_role_argv

    run, _ = sound
    # tests/conftest.py empties the program's one config between tests; a run has it throughout
    parse_role_argv(["-config", os.path.join(work, "job.conf")])
    counts = run.model.window_counts(run)
    steps, layers, experts = counts.shape
    assert steps == run.counters["steps"] > 0 and (layers, experts) == (2, 16)
    assert (counts.sum(axis=-1) == 2 * 2 * 64 * 3).all()  # both copies of every token chose three


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


def test_weights_come_from_init_seed_and_feed_and_noise_from_the_runs(sound, tmp_path):
    import dataclasses

    import numpy as np

    run, _ = sound
    adapter = run.extra["adapter"]
    assert adapter.weights_seed == run.config["init"]["seed"]
    other = dataclasses.replace(run, seed=run.seed + 1)
    same = type(adapter)(other, adapter.trainer)
    mine, theirs = adapter._weights(), same._weights()
    assert all(np.array_equal(mine[k], theirs[k]) for k in mine)
    feeds = []
    for r, name in ((run, "a"), (other, "b")):
        os.makedirs(tmp_path / name)
        feeds.append(np.load(type(adapter).dataset(r, str(tmp_path / name))))
    assert feeds[0].shape == feeds[1].shape and not np.array_equal(feeds[0], feeds[1])
    assert feeds[0].max() < 255 and (feeds[0] == 0).any()
    with open(CONFIG) as f:
        assert json.load(f)["init"] == {"std": 0.02, "seed": 34}


def test_configuration_keeps_the_published_widths():
    with open(CONFIG) as f:
        config = json.load(f)
    keys, published = config["keys"], config["published"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert published == {k: PUBLISHED[k] for k in config["reduced"]}
    for k, v in PUBLISHED.items():  # every key of the catalog row, changed only where 'reduced' says
        assert k in config, k
        assert (config[k] == v) != (k in config["reduced"]), k
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
              "rms_norm_eps", "moe_intermediate_size", "num_experts_per_tok", "num_hidden_layers",
              "vocab_size"):
        assert keys[k] == config[k], k  # the program runs what the file states
    assert (keys["hidden_size"], keys["num_attention_heads"], keys["num_key_value_heads"], keys["head_dim"],
            keys["moe_intermediate_size"], keys["num_experts_per_tok"], keys["rope_theta"],
            keys["rms_norm_eps"]) == (2048, 32, 4, 128, 768, 8, 1000000, 1e-6)
    assert keys["router_experts"] == published["num_experts"] == 128
    assert keys["experts_held"] == config["num_experts"] == 16 == published["num_experts"] // 8
    assert keys["vocab_size"] * 8 == published["vocab_size"] and keys["mask_token_id"] == keys["vocab_size"] - 1
    assert keys["num_hidden_layers"] == 6 >= 4 and keys["first_k_dense_replace"] == keys["n_shared_experts"] == 0
    assert (keys["seq_len"], keys["batch_size"], keys["block_length"]) == (4096, 1, 4)
    assert {"block_length", "noise", "mask_token_id", "init", "optimizer_values", "feed"} <= set(config["assumed"])
    assert config["departures"] and config["guarantees"] and "eight chips" in config["deployment"]
    with open(os.path.join(ROOT, "benchmark", "traffic", "train-bd-4k.json")) as f:
        mix = json.load(f)
    assert mix["job"] == "train" and mix["keys"]["seq_len"] == 4096 and mix["keys"]["block_length"] == 4


def test_operations_and_parameters_by_hand(bench):
    from lib import jobs

    model = jobs.load_model("sdar")
    with open(CONFIG) as f:
        keys = json.load(f)["keys"]
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    assert attention + 2 * 128 + 2 * 2048 == 18_878_720
    expert = 3 * 2048 * 768
    assert expert == 4_718_592 and 16 * expert == 75_497_472
    layer = 18_878_720 + 2048 * 128 + 16 * expert
    assert layer == 94_638_336 and 18_878_720 + 2048 * 128 + 128 * expert == 623_120_640
    held = 6 * layer + 2 * 18992 * 2048 + 2048
    assert model.parameters_held(keys) == held == 645_623_296  # 10.33 GB at 16 B
    assert model.parameters_held({**keys, "num_hidden_layers": 5}) == held - layer  # 8.82 GB
    # a token is two positions through the layers and one through the head
    position = attention + 2048 * 128 + 8 * 16 / 128 * expert
    assert model.matrix_parameters_per_position(keys) == pytest.approx(position)
    pairs = 32 * 4096 * (4096 + 4)
    assert model.attention_flops_per_token(keys) * 4096 == pytest.approx(6 * 2 * pairs * 256)
    per_token = 6 * (2 * 6 * position + 2048 * 18992) + 3 * 6 * 2 * 32 * 256 * 4100
    assert model.flops_per_item(keys) == pytest.approx(per_token)
    assert model.flops_per_item(keys) * 4096 == pytest.approx(1.294e13, rel=1e-3)  # a step, no rematerialisation
    assert model.attention_kernel_flops_per_step(keys) == pytest.approx(6 * 2 * pairs * (2 * 256 + 384 + 512))
    from swiftsnails_tpu.ops.flash_attention import attention_flops

    mine = attention_flops(8192, 32, 128, 128, diffusion_block=4)  # the program's count, the benchmark's restated
    assert model.attention_kernel_flops_per_step(keys) == pytest.approx(
        6 * (2 * mine["fwd"] + mine["dq"] + mine["dkv"]))
    assert model.experts_kernel_flops(keys, 1000.0) == pytest.approx(1000 * 3 * 2 * 2048 * 768 * 4)
    keep = model.allowed_pairs(64, 4)
    assert keep.shape == (128, 128) and keep.sum() == 64 * 68
    assert not keep[64:, :64].any()  # a clean query never sees the noised copy
    assert keep[5, 4:8].all() and not keep[5, :4].any() and keep[5, 64:68].all() and not keep[5, 68:].any()
    assert keep[64 + 5, 64:72].all() and not keep[64 + 5, 72:].any()


def test_entries_are_appended_and_resolve(bench):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        record = json.load(f)
    names = [m["name"] for m in record["per_layer"]]
    at = names.index(PR34[0])
    assert names[at:at + len(PR34)] == PR34 and at > names.index("train.drain_s")
    by_name = {m["name"]: m for m in record["per_layer"]}
    for name, source in zip(PR34, ("device_trace", "program_counter")):
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_items_per_s" and m["layer"] == "model step"
        assert m["source"] == source
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    for name in REPORTED:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in ("step.mlp_ms", "kernel.train_roofline", "step.prep_ms"):
        assert CELL not in by_name[name]["workloads"]
    rate = next(m for m in record["end_to_end"] if m["name"] == "train_items_per_s")
    assert rate["workloads"][-1] == CELL and rate["bound"] == 0.01
    cell = record["workloads"][-1]
    assert cell == {"name": CELL, "config": "sdar-30b-a3b", "traffic": "train-bd-4k", "chips": 1,
                    "why": cell["why"]} and len(cell["why"]) <= 200
    entry = record["configs"][-1]
    with open(CONFIG) as f:
        config = json.load(f)
    assert entry["name"] == "sdar-30b-a3b" and entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] and entry["file"] == "benchmark/configs/sdar-30b-a3b.json"
    # a run of another program, or none of the counters: no value, no error
    from lib import jobs

    empty = jobs.Run(config={"model": "widedeep", "keys": {}}, mix={}, seed=1, seconds=1.0, traced=True)
    for name in PR34:
        assert bench.load_reader(name)(empty) is None, name
