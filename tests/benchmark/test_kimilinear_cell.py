"""The `kimi-linear-48b-a3b` configuration, its family, the
`kimilinear-8k` cell and the readers PR 65 adds, on the CPU: the files
and BENCHMARK.json agree (entries looked up BY NAME: the next cell is
appended after this one), the configuration holds the catalog's numbers
and exactly its cuts, the family's map onto the builder, `train_flops`
and the kernel counts against hand counts, each reader on a fixture and
without a trace, the parity script's arithmetic at a toy size, and a toy
cell through `run_cell`.  No number from here is a speed.
"""

import importlib.util
import inspect
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
FIXTURES = os.path.join(HERE, "fixtures")
sys.path.insert(0, BENCH)

import kernel_counts  # noqa: E402
import kernel_counts_kimi_linear as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
CELL, CONFIG = "kimilinear-8k", "kimi-linear-48b-a3b"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")
CATALOG = {      # the catalog row's `config`, Kimi-Linear-48B-A3B-Instruct
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "linear_attn_config"]
NEW_READERS = {
    "device_ms_per_step.channel_delta_attention": "device_trace",
    "channel_delta_roofline_share": "device_trace",
    "channel_delta_chunks_per_step": "program_counter",
    "device_ms_per_step.latent_attention_unrotated": "device_trace",
    "device_ms_per_step.routed_ffn_w1024": "device_trace",
    "held_expert_row_share_w1024": "program_counter"}
T, D, V, LAYERS, HELD, RANKS = 8192, 2304, 20480, 5, 8, 32
H, DK, F, DENSE = 32, 128, 1024, 9216
PARAMETERS = 602433408


def real():
    return bench_run.load_cell(CELL, (BENCH,))


def reader(name):
    return bench_run.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"))


def load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_configuration_holds_the_published_numbers_and_exactly_its_cuts():
    _, config, _ = real()
    differs = [k for k, v in CATALOG.items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED) == sorted(config["reduced"])
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (LAYERS, HELD, V)
    assert V == 163840 // 8                         # the floor of an eighth
    # inside the one nested group that is cut: the two layer lists, to
    # the published layers 1-5, and no width
    group, published = config["linear_attn_config"], CATALOG[
        "linear_attn_config"]
    assert {k for k in published if group[k] != published[k]} == {
        "kda_layers", "full_attn_layers"}
    assert group["kda_layers"] == [i for i in published["kda_layers"]
                                   if i <= LAYERS] == [1, 2, 3, 5]
    assert group["full_attn_layers"] == [4]
    assert config["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "linear_attn_config": config["published"]["linear_attn_config"]}
    assert (config["expert_parallel_size"], config["expert_parallel_rank"],
            config["sequence_length"]) == (RANKS, 0, T)
    assert HELD * RANKS == 256
    # the plan the rule read, and the cut that would not fit
    assert "12.69 GB" in config["reduced_why"]
    assert "7.23 arguments + 5.46 temporaries" in config["reduced_why"]
    assert "9.95 GB" in config["reduced_why"]
    assert f"{PARAMETERS:,}" in config["reduced_why"]
    assert "32 chips share each layer" in config["deployment"]
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["expert_bias_update_rate"],
            t["recompute"], t["use_amp"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, 0.01, "layer", True)
    assert "initializer_range" not in t and "embedding_init_range" not in t
    assert {"layer_types", "projection biases", "delta mixer", "gate rank",
            "A_log / dt_bias / w_o", "column order", "latent attention",
            "unread keys", "selection bias", "moe_renormalize",
            "num_expert_group / topk_group", "auxiliary loss",
            "prediction module", "packed documents", "router update",
            "weights", "training", "sequence_length", "recomputation"} \
        <= set(config["assumed"])
    assert "float32" in config["precision"]


def test_the_family_maps_the_published_keys_onto_the_builder():
    from paddle_tpu.models import decoder

    _, config, family = real()
    args = family.architecture(config)
    delta = "channel_delta_attention"
    assert args["layer_types"] == [delta] * 3 + ["full_attention", delta]
    assert (args["router"], args["use_expert_bias"], args["qk_norm"],
            args["norm_topk_eps"]) == ("sigmoid", True, None, 1e-20)
    assert (args["num_experts_per_tok"], args["norm_topk_prob"],
            args["n_shared_experts"], args["num_dense_layers"]) == (
        8, True, 1, 1)
    assert args["q_lora_rank"] is None and args["mla_use_nope"] is True
    assert args["linear_attn_config"] == config["linear_attn_config"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "rms_norm_eps", "routed_scaling_factor",
                "tie_word_embeddings", "num_expert_group", "topk_group"):
        assert args[key] == CATALOG[key], key
    assert (args["num_experts"], args["expert_parallel_size"],
            args["expert_parallel_rank"]) == (HELD, RANKS, 0)
    assert not {"model_type", "hidden_act", "head_dim", "rope_theta",
                "rope_scaling", "model_max_length", "moe_layer_freq",
                "use_grouped_topk"} & set(args)
    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    assert set(config["training"]) <= (
        set(inspect.signature(decoder.build_model).parameters)
        | set(inspect.signature(decoder.decoder).parameters))
    # no model's name in the program
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    text = f.read().lower()
                assert "kimi_linear" not in text and "kimilinear" not in text


def test_parameters_by_hand():
    """A delta mixer 39,514,272, latent attention 29,114,880, an expert
    7,077,888: 602.4 M parameters at 8 held = 7.23 GB of float32 master
    weights and two Adam moments, 9.64 GB with gradients; 828.9 M = 9.95
    GB at 16 held."""
    lanes = H * DK
    delta = (D * 3 * lanes + 3 * lanes * 4 + 2 * (D * DK + DK * lanes)
             + lanes + H + D * H + DK + lanes * D)
    latent = D * H * 192 + D * (512 + 64) + 512 + 512 * H * 256 + lanes * D
    assert (delta, latent, 3 * D * F) == (39514272, 29114880, 7077888)

    def sparse(held):
        return held * 3 * D * F + 3 * D * F + D * 256

    def total(held):
        return (delta + 3 * D * DENSE + 3 * (delta + sparse(held))
                + latent + sparse(held) + LAYERS * 2 * D + 2 * V * D + D)

    assert total(8) == PARAMETERS
    assert round(12 * total(8) / 1e9, 2) == 7.23
    assert round(16 * total(8) / 1e9, 2) == 9.64
    assert round(12 * total(16) / 1e9, 2) == 9.95


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (CONFIG, "b1-len8192-host", 1, None, 1, T,
                              "host", 4)
    assert len(cell["why"]) <= 200 and "1/32" in cell["why"]
    # the traffic is granite4h-8k's own, key for key
    other = bench_run.load_json(os.path.join(BENCH, "workloads",
                                             "granite4h-8k.json"))
    assert {k: v for k, v in cell.items()
            if k not in ("config", "why", "name")} \
        == {k: v for k, v in other.items() if k not in ("config", "why")}
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert CELL in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == CELL] == [{
        "name": CELL, "config": CONFIG, "traffic": "b1-len8192-host",
        "chips": 1, "why": cell["why"]}]
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": T, "unit": "tokens/s"}}


def test_train_flops_count_the_recurrence_in_its_sequential_form():
    """ISSUE 65's table from the shapes: 768 M forward FLOP a token,
    18.9 TFLOP a step."""
    cell, config, family = real()
    lanes = H * DK
    want = {
        "delta_projections": 4 * 2 * (D * 3 * lanes
                                      + 2 * (D * DK + DK * lanes)
                                      + D * H + lanes * D),
        "recurrence": 4 * H * 6 * DK * DK,
        "latent_projections": 2 * (D * H * 192 + D * 576 + 512 * H * 256
                                   + lanes * D),
        "latent_attention": 2 * (T + 1) * H * 320 / 2,
        "dense_ffn": 3 * 2 * D * DENSE,
        "router": 4 * 2 * D * 256,
        "shared_expert": 4 * 3 * 2 * D * F,
        "experts": 4 * 8 / RANKS * 3 * 2 * D * F,
        "head": 2 * D * V}
    got = family.forward_flops_per_token(config, T)
    assert got == pytest.approx(want)
    m = {k: round(v / 1e6) for k, v in got.items()}
    assert (m["delta_projections"], m["recurrence"]) == (316, 13)
    assert (m["latent_projections"], m["latent_attention"]) == (58, 84)
    assert (m["dense_ffn"], m["head"]) == (127, 94)
    assert round((got["router"] + got["shared_expert"] + got["experts"])
                 / 1e6, 1) == 75.5
    total = sum(got.values())
    assert round(total / 1e6) == 768
    assert family.train_flops(config, cell) == pytest.approx(3 * total * T)
    assert family.train_flops(config, cell) == pytest.approx(18.9e12,
                                                             rel=3e-3)
    # the sequential count does not know the chunk
    assert "CHUNK" not in open(os.path.join(
        BENCH, "models", "kimi_linear.py")).read()


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    assert counts.delta_layers(config) == 4
    assert counts.chunks_per_call(config, cell) == 32 * 128 == 4096
    flops, nbytes = counts.channel_delta_cost(config, cell)
    assert flops == 4 * 3 * T * H * 6 * DK * DK
    lanes = T * H * DK
    forward = lanes * (3 * 2 + 4 + 2) + T * H * 4
    assert nbytes == 4 * (2 * forward + lanes * (2 + 6 + 4) + T * H * 4)
    # the bytes bound it on a v5e: 5.9 ms against 1.6 ms of products
    assert nbytes / 819e9 > 3 * flops / 197e12
    assert round(1e3 * nbytes / 819e9, 1) == 5.9
    # every kernel's name carries the prefix the share reads by, and no
    # other family's scan reader takes one for its own
    from paddle_tpu.ops.pallas import KERNEL_COSTS, channel_delta  # noqa: F401

    registered = sorted(k for k in KERNEL_COSTS
                        if k.startswith(counts.DELTA_KERNELS))
    assert registered == sorted(counts.DELTA_KERNEL_NAMES)
    assert not any(k.startswith(("gated_delta_fwd", "gated_delta_bwd"))
                   for k in registered)
    shapes = [((H, T, DK), 2)] * 4
    fwd, _ = KERNEL_COSTS["channel_delta_fwd"](shapes, None)
    bwd, _ = KERNEL_COSTS["channel_delta_bwd"](shapes, None)
    assert fwd + bwd == H * T * (9 * 2 * DK * DK + 3 * 2 * 64 * DK)


def test_make_batch_draws_shifted_views_of_the_vocabulary_slice():
    cell, config, family = real()
    a = family.make_batch(config, cell, np.random.default_rng(2**31 + 5))
    b = family.make_batch(config, cell, np.random.default_rng(2**31 + 5))
    assert sorted(a) == ["labels", "tokens"]
    for key in a:
        assert a[key].shape == (1, T) and a[key].dtype == np.int64
        assert 1 <= a[key].min() and a[key].max() < V
        np.testing.assert_array_equal(a[key], b[key])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    with pytest.raises(ValueError, match="sequence_length"):
        family.make_batch(config, dict(cell, length=4096),
                          np.random.default_rng(0))


def test_new_readers_match_benchmark_json_and_read_none_without_a_trace():
    listed = {m["name"]: m for m in benchmark_json()["per_layer"]}
    assert set(NEW_READERS) <= set(listed)
    cell, config, _ = real()
    no_trace = {"cell": cell, "config": config, "trace": None, "steps": 5}
    for name, source in NEW_READERS.items():
        module = reader(name)
        assert module.META["cells"] == [CELL] == listed[name]["workloads"]
        assert module.META["moves"] == "mfu" == listed[name]["moves"]
        assert module.META["unit"] == listed[name]["unit"]
        assert module.META["layer"] == listed[name]["layer"]
        assert module.META["source"] == source == listed[name]["source"]
        if source == "device_trace":
            assert module.compute(no_trace) is None
    readers = bench_run.layer_readers(CELL, (BENCH,))
    everywhere = {m["name"] for m in benchmark_json()["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert CELL in readers[name].META["cells"]
    for other in ("joyai-8k", "qwen3next-16k"):
        assert not set(NEW_READERS) & set(
            bench_run.layer_readers(other, (BENCH,)))


def rows_fixture():
    """Rows as `observe/trace.op_rows` gives them for 2 traced steps."""
    def row(instruction, bucket, self_s, scope="", op_type=None,
            kernel=None, flops=0.0):
        return {"module": "jit_step(1)", "instruction": instruction,
                "bucket": bucket, "self_s": self_s, "calls": 2,
                "op_type": op_type, "name_scope": scope, "op_name": "",
                "phase": "backward", "flops": flops, "kernel": kernel,
                "joined": True}

    delta = "channel_delta_attention"
    return [
        row("fusion.1", "matmul", 0.050, delta, "mul", None, 3e9),
        row("fusion.2", "elementwise", 0.030, "checkpoint/" + delta,
            "channel_delta_rule"),
        row("custom-call.1", "custom_call", 0.020, delta,
            "channel_delta_rule", "channel_delta_inverse"),
        row("custom-call.2", "custom_call", 0.010, delta,
            "channel_delta_rule", "channel_delta_operands_fwd"),
        row("custom-call.3", "custom_call", 0.030, delta,
            "channel_delta_rule", "channel_delta_operands_bwd"),
        row("custom-call.4", "custom_call", 0.016, delta,
            "channel_delta_rule", "channel_delta_fwd"),
        row("custom-call.5", "custom_call", 0.024, delta,
            "channel_delta_rule", "channel_delta_bwd"),
        row("custom-call.6", "custom_call", 0.008, delta, "short_conv",
            "short_conv_fwd"),
        row("fusion.3", "matmul", 0.040, "latent_attention", "mul", None,
            2e9),
        row("custom-call.7", "custom_call", 0.060, "latent_attention",
            "latent_attention", "flash_mla_fwd"),
        row("fusion.4", "matmul", 0.070, "", "moe_dropless", None, 1e9),
        row("custom-call.8", "custom_call", 0.012, "", "moe_dropless",
            "ragged_dot"),
        row("fusion.5", "matmul", 0.018, "shared_expert", "mul"),
        row("fusion.6", "elementwise", 0.005, "", "adam"),
    ]


@pytest.fixture
def traced(monkeypatch):
    cell, config, _ = real()
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows_fixture())
    return {"cell": cell, "config": config, "steps": 2,
            "trace": {"path": "x", "chip0": {"lo": 0.0, "hi": 1.0,
                                             "steps": 2}}}


def test_readers_on_a_fixture(traced, monkeypatch):
    assert reader("device_ms_per_step.channel_delta_attention").compute(
        traced) == pytest.approx((50 + 30 + 20 + 10 + 30 + 16 + 24 + 8) / 2)
    assert reader("device_ms_per_step.latent_attention_unrotated").compute(
        traced) == pytest.approx((40 + 60) / 2)
    assert reader("device_ms_per_step.routed_ffn_w1024").compute(
        traced) == pytest.approx((70 + 12 + 18) / 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    flops, nbytes = counts.channel_delta_cost(traced["config"],
                                              traced["cell"])
    # the five kernels' 100 ms over two steps; not the convolution's
    want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / 50.0
    assert reader("channel_delta_roofline_share").compute(traced) \
        == pytest.approx(want)
    assert 0 < want < 100
    # a program whose rows carry no name scope and no such kernel (the
    # parent's) reads nothing and does not raise

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture() if not (r["kernel"] or "").startswith(
                    "channel_delta")]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    for name in ("device_ms_per_step.channel_delta_attention",
                 "device_ms_per_step.latent_attention_unrotated",
                 "device_ms_per_step.routed_ffn_w1024",
                 "channel_delta_roofline_share"):
        assert reader(name).compute(traced) is None, name


def test_the_counters_read_the_programs_own(monkeypatch):
    from paddle_tpu.observe import routing
    from paddle_tpu.observe.monitoring import runtime_stats

    chunks = reader("channel_delta_chunks_per_step")
    monkeypatch.setattr(runtime_stats, "channel_delta_calls", 0)
    monkeypatch.setattr(runtime_stats, "channel_delta_chunks", 0)
    assert chunks.compute({}) is None        # the XLA lowering: no call
    for _ in range(12):
        runtime_stats.record_channel_delta(4096)
    assert chunks.compute({}) == 12 * 4096 == 49152
    assert counts.scan_chunks() == (12, 49152)
    share = reader("held_expert_row_share_w1024")
    monkeypatch.setattr(routing, "held_row_share", lambda: 0.03125)
    assert share.compute({}) == 3.125
    monkeypatch.setattr(routing, "held_row_share", lambda: None)
    assert share.compute({}) is None
    # a program from before the counters: nothing to read, no raise
    monkeypatch.setattr(type(runtime_stats), "snapshot", lambda self: {})
    assert chunks.compute({}) is None


def test_toy_cell_runs_the_harness(capfd):
    result = bench_run.run_cell("tiny-kimi-linear-host", 2**31 + 11, 5.0,
                                True, roots=(BENCH, FIXTURES),
                                device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out;
    # heads of 16 run the scan's XLA lowering: no chunk count either
    assert set(result["metrics"]) >= {"dispatch_ms.train",
                                      "compiles_in_window"}
    assert "channel_delta_chunks_per_step" not in result["metrics"]
    assert '"loss_fell": true' in capfd.readouterr().out


def test_parity_script_compares_logits_routing_and_every_leaf(monkeypatch):
    parity = load("kimi_linear_parity")
    _, config, family = bench_run.load_cell("tiny-kimi-linear-host",
                                            (BENCH, FIXTURES))
    monkeypatch.setattr(parity.base, "LAST", 16)
    monkeypatch.setattr(parity.base, "Q_BLOCK", 16)
    monkeypatch.setattr(parity.base, "GRAD_Q_BLOCK", 16)
    r = parity.check_seed(config, family, 2**31 + 3)
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_loss"]
    assert checks["f32_routing"] and checks["f32_held_counts"]
    assert checks["share_is_a_share"] and checks["grads_are_compared"]
    assert checks["f32_grads"], r["f32"]["grad_err_worst_leaf"]
    assert len(r["f32"]["grad_err"]) == len(
        parity.reference.system_names(config))
    assert r["f32"]["grad_dead_leaves"] == [
        f"layer{i}.router" for i in range(1, 5)]
    # the control: the state in bfloat16 is seen by the comparison
    from paddle_tpu.ops.pallas import channel_delta

    # (monkeypatch puts the module's own step back after the test)
    monkeypatch.setattr(channel_delta, "_chunk_step",
                        channel_delta._chunk_step)
    parity.state_in_bfloat16()
    control = parity.check_seed(config, family, 2**31 + 3, control=True)
    assert control["f32"]["grad_err_worst"] > 10 * r["f32"]["grad_err_worst"]
