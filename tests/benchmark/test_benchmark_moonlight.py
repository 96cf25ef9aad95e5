"""Moonlight-16B-A3B's cell at a size a test run can hold: the tiny cell
through ``run.execute`` on the CPU (the kernels in interpret mode), the
control and every fault the reference can plant judged at the tiny cell's
limits (each has to come out not correct), the configuration's file against
its own ``published`` and ``keys``, the operations by hand, and the entries
this cell added to ``BENCHMARK.json``."""

import importlib.util
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
import tiny_tree  # noqa: E402  (conftest.py has told it this cell's names)

CELL = "moonlight-16b-a3b.train-8k"
TINY = "tiny-moonlight.tiny-train-8k"
PR26 = ["entry.build_trainer_s", "entry.data_load_s", "train.producer_busy_share", "train.h2d_share",
        "train.finalize_s", "step.device_ms", "step.prep_ms", "step.fused_ms", "step.pull_ms",
        "step.push_ms", "step.dense_ms", "step.unscoped_ms"]
PR28 = ["step.attn_ms", "step.mlp_ms", "step.route_ms", "step.experts_ms", "step.head_ms",
        "step.opt_ms", "kernel.experts_roofline", "kernel.attn_roofline", "moe.held_share",
        "moe.load_max_over_mean", "moe.dropped"]
LIMITS = {"loss_step1": 3e-3, "loss_step2": 3e-3, "loss_step3": 3e-3, "grad1_worst_leaf": 0.025,
          "change3_worst_leaf": 0.01, "route_disagree_share": 0.05}
FAULTS = ["half_batch", "state_unchanged", "five_experts", "no_route_scale", "no_shared",
          "no_rotary", "capacity_drop", "no_bias_step"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    tree = tiny_tree.build(str(tmp_path_factory.mktemp("moonlight") / "tree"))
    with open(os.path.join(tree, "benchmark", "limits", TINY + ".json"), "w") as f:
        json.dump({"limits": LIMITS}, f)
    sys.path.insert(0, os.path.join(tree, "benchmark"))
    spec = importlib.util.spec_from_file_location(
        "bench_run_moonlight", os.path.join(tree, "benchmark", "run.py"))
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
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) == {"loss_step1", "loss_step2", "loss_step3", "grad1_worst_leaf",
                                     "change3_worst_leaf", "route_disagree_share"}
    got = {k: v["value"] for k, v in line["metrics"].items()}  # a rehearsal prints counts alone
    assert set(got) == {"entry.compiles_in_window", "moe.held_share", "moe.load_max_over_mean",
                        "moe.dropped"}
    assert got["moe.dropped"] == 0.0 and got["entry.compiles_in_window"] == 0.0
    assert 15.0 < got["moe.held_share"] < 35.0  # 4 of 16 experts held: 25% give or take the batches' skew
    assert 1.0 <= got["moe.load_max_over_mean"] < 4.0
    leaves = run.counters["readings"]["program"]["change"]
    assert "router_bias" in leaves and "moe.experts_down" in leaves and "dense.wkv_a" in leaves


def test_the_window_is_counted_by_running_its_steps_again(sound, work):
    """``kernel.experts_roofline``'s operations: the replay counts for the
    warm steps what the run counted (it raises if not), then the window's."""
    from swiftsnails_tpu.utils.flags import parse_role_argv

    run, _ = sound
    # tests/conftest.py empties the program's one config between tests; a run has it throughout
    parse_role_argv(["-config", os.path.join(work, "job.conf")])
    counts = run.model.window_counts(run)
    steps, layers, experts = counts.shape
    assert steps == run.counters["steps"] > 0 and (layers, experts) == (2, 16)
    assert (counts.sum(axis=-1) == 2 * 64 * 3).all()  # every token chose three, held or not
    assert run.model.window_counts(run) is counts  # once a run


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
    if part in set(adapter.parts()) | {"half_batch"}:
        assert part in FAULTS  # control.py reads every one of them


def test_weights_come_from_init_seed_and_the_feed_from_the_runs(sound, tmp_path):
    """The configuration's ``init.seed`` makes the weights, the same in
    every run, so that the experts a sequence flocks to are drawn once;
    ``--seed`` still changes the feed."""
    import dataclasses

    import numpy as np

    run, _ = sound
    adapter = run.extra["adapter"]
    assert adapter.weights_seed == run.config["init"]["seed"]
    other = dataclasses.replace(run, seed=run.seed + 1)
    repinned = dataclasses.replace(run, config={**run.config, "init": {**run.config["init"], "seed": 28}})
    same, moved = type(adapter)(other, adapter.trainer), type(adapter)(repinned, adapter.trainer)
    mine, theirs = adapter._weights(), same._weights()
    assert all(np.array_equal(mine[k], theirs[k]) for k in mine)
    assert not np.array_equal(mine["embed"], moved._weights()["embed"])
    feeds = []
    for r, name in ((run, "a"), (other, "b")):
        os.makedirs(tmp_path / name)
        feeds.append(np.load(type(adapter).dataset(r, str(tmp_path / name))))
    assert feeds[0].shape == feeds[1].shape and not np.array_equal(feeds[0], feeds[1])
    with open(os.path.join(ROOT, "benchmark", "configs", "moonlight-16b-a3b.json")) as f:
        assert json.load(f)["init"] == {"std": 0.02, "seed": 28}


def test_configuration_keeps_the_published_widths():
    with open(os.path.join(ROOT, "benchmark", "configs", "moonlight-16b-a3b.json")) as f:
        config = json.load(f)
    keys, published = config["keys"], config["published"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert published == {"num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840}
    for k in ("hidden_size", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps", "intermediate_size",
              "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok",
              "routed_scaling_factor", "first_k_dense_replace", "num_hidden_layers", "vocab_size"):
        assert keys[k] == config[k], k  # the program runs what the file states
    assert (config["hidden_size"], config["moe_intermediate_size"], config["kv_lora_rank"]) == (2048, 1408, 512)
    assert keys["router_experts"] == published["n_routed_experts"] == 64
    assert keys["experts_held"] == config["n_routed_experts"] == 8 == published["n_routed_experts"] // 8
    assert keys["vocab_size"] * 8 == published["vocab_size"] and keys["seq_len"] == config["max_position_embeddings"]
    assert config["assumed"] and config["departures"] and config["guarantees"] and "eight chips" in config["deployment"]


def test_operations_and_parameters_by_hand(bench):
    from lib import jobs

    model = jobs.load_model("moonlight")
    with open(os.path.join(ROOT, "benchmark", "configs", "moonlight-16b-a3b.json")) as f:
        keys = json.load(f)["keys"]
    attention = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attention == 13_762_560  # and two norms' 2,048 + the latent norm's 512 beside it
    layer = attention + 2 * 2048 + 512
    dense = layer + 3 * 2048 * 11264
    mixture = layer + 2048 * 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408
    held = dense + 5 * mixture + 2 * 20480 * 2048 + 2048
    assert model.parameters_held(keys) == held == 668_890_112  # 10.70 GB at 16 B
    touched = 6 * attention + 3 * 2048 * 11264 + 5 * (2048 * 64 + 3 * 2048 * 2816
                                                     + 6 * 8 / 64 * 3 * 2048 * 1408) + 2048 * 20480
    assert model.matrix_parameters_per_token(keys) == pytest.approx(touched) == pytest.approx(313.33e6, rel=1e-4)
    causal = 2 * 6 * 16 * (192 + 128) * 8193 / 2
    assert model.flops_per_item(keys) == pytest.approx(6 * touched + 3 * causal) == pytest.approx(2.635e9, rel=1e-3)
    pairs = 16 * 8192 * 8193 / 2
    assert model.attention_kernel_flops_per_step(keys) == pytest.approx(
        6 * 2 * pairs * (2 * 320 + 512 + 640))
    assert model.experts_kernel_flops(keys, 1000.0) == pytest.approx(1000 * 3 * 2 * 2048 * 1408 * 4)
    assert model.disagree_share([[[[1, 2], [3, 4]]]], [[[[2, 1], [3, 5]]]]) == pytest.approx(0.25)
    ids = model.token_ids(50000, 20480, 1.05, 600.0, 1.0, 7)
    assert ids.min() == 0 and ids.max() < 20480 and 30 < (ids == 0).sum() < 200
    assert (model.token_ids(50000, 20480, 1.05, 600.0, 1.0, 7) == ids).all()


def test_entries_are_appended_and_resolve(bench):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        record = json.load(f)
    names = [m["name"] for m in record["per_layer"]]
    assert names[-len(PR28):] == PR28 and names[-len(PR28) - len(PR26):-len(PR28)] == PR26
    by_name = {m["name"]: m for m in record["per_layer"]}
    for name in PR28:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "train_items_per_s"
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
        assert m["source"] == ("program_counter" if name.startswith("moe.") else "device_trace")
    assert CELL not in by_name["kernel.train_roofline"]["workloads"]
    for name in ("train.step_mfu", "train.input_wait_share", "device.idle_share.train",
                 "step.device_ms", "step.unscoped_ms", "entry.data_load_s"):
        assert by_name[name]["workloads"][-1] == CELL
    cell = {w["name"]: w for w in record["workloads"]}[CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    # a run of another program, or none of the counters: no value, no error
    from lib import jobs

    empty = jobs.Run(config={"model": "widedeep", "keys": {}}, mix={}, seed=1, seconds=1.0, traced=True)
    for name in PR28:
        assert bench.load_reader(name)(empty) is None, name
