"""The `laguna-xs.2` configuration, its family, the `laguna-16k` cell and
the readers PR 51 adds, on the CPU: the files and BENCHMARK.json agree
(entries looked up BY NAME, never by position: the next cell is
appended after this one), the configuration holds the catalog's numbers
and exactly its six cuts, the family's map onto the builder,
`train_flops` and the kernel counts against hand counts, each reader on
a fixture and without a trace, the parity script's arithmetic at a toy
size, and a toy cell through `run_cell`.  No number from here is a
speed.
"""

import importlib.util
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
import kernel_counts_laguna as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
    "original_max_position_embeddings": 4096}
CATALOG = {      # the catalog row's `config`, Laguna-XS.2
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": ROPE, "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_attention_heads_per_layer", "num_experts", "vocab_size"]
NEW_READERS = {
    "device_ms_per_step.sliding_attention_64h": "device_trace",
    "device_ms_per_step.full_attention_48h": "device_trace",
    "flash_window512_roofline_share": "device_trace",
    "flash_grouped_48h_roofline_share": "device_trace",
    "flash_window512_fill_share": "program_counter",
    "device_ms_per_step.attention_head_gate": "device_trace",
    "device_ms_per_step.routed_ffn_256": "device_trace",
    "held_expert_row_share_256": "program_counter",
    "expert_matmul_w512_roofline_share": "device_trace"}
T, D, HKV, HD, W, F, V = 16384, 2048, 8, 128, 512, 512, 12544
HEADS = [48, 64, 64, 64, 48]
CAUSAL, BAND = 134225920, 8257792


def real():
    return bench_run.load_cell("laguna-16k", (BENCH,))


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
    assert config["num_hidden_layers"] == 5     # layer 0 and one period
    assert config["layer_types"] == PERIOD + ["full_attention"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == HEADS
    assert (config["num_experts"], config["vocab_size"]) == (32, V)
    published = config["published"]
    assert (published["num_experts"], published["vocab_size"],
            published["num_hidden_layers"]) == (256, 100352, 40)
    assert (config["expert_parallel_size"], config["expert_parallel_rank"],
            config["sequence_length"]) == (8, 0, T)
    # no width, no head count, no window, no RoPE key is cut
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "shared_expert_intermediate_size", "intermediate_size",
                "num_experts_per_tok", "sliding_window", "rope_parameters",
                "partial_rotary_factor", "moe_routed_scaling_factor"):
        assert config[key] == CATALOG[key], key
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == "laguna-xs.2"]
    assert len(entry) == 1
    entry = entry[0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/laguna-xs.2.json"
    assert len(entry["why"]) <= 200
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["recompute"], t["use_amp"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, "layer", True)
    assert {"gate", "qk_norm", "router", "shared expert", "hidden_act",
            "window edge", "rope", "head counts", "prediction module",
            "unread keys", "router update", "weights", "training",
            "sequence_length", "recomputation"} <= set(config["assumed"])
    assert "8 chips share each layer" in config["deployment"]
    # `mellum2`'s init: a unit-variance table under small matrices
    assert (t["initializer_range"], t["embedding_init_range"]) == (0.002, 1.0)


def test_the_family_maps_the_published_keys_onto_the_builder():
    _, config, family = real()
    args = family.architecture(config)
    assert (args["qk_norm"], args["router"], args["norm_topk_prob"],
            args["attention_gate"]) == ("head", "softmax", True, "head")
    assert args["routed_scaling_factor"] == 2.5
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "shared_expert_intermediate_size", "intermediate_size",
                "num_experts_per_tok", "rms_norm_eps", "sliding_window",
                "rope_parameters", "partial_rotary_factor",
                "tie_word_embeddings"):
        assert args[key] == CATALOG[key], key
    assert args["num_attention_heads_per_layer"] == HEADS
    assert (args["num_experts"], args["expert_parallel_size"],
            args["expert_parallel_rank"]) == (32, 8, 0)
    assert not {"model_type", "gating", "hidden_act",
                "moe_apply_router_weight_on_input",
                "moe_routed_scaling_factor",
                "max_position_embeddings"} & set(args)
    import inspect

    from paddle_tpu.models import decoder

    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    assert set(config["training"]) <= (
        set(inspect.signature(decoder.build_model).parameters)
        | set(inspect.signature(decoder.decoder).parameters))
    for key, value in (("hidden_act", "gelu"), ("attention_bias", True),
                       ("gating", False),
                       ("moe_apply_router_weight_on_input", True)):
        with pytest.raises(NotImplementedError, match=key):
            family.architecture(dict(config, **{key: value}))
    # no model's name in the program
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    assert "laguna" not in f.read().lower(), name


def test_parameters_by_hand():
    """691.6 M parameters: 8.30 GB of float32 master weights and two
    Adam moments, 11.07 GB with a float32 gradient beside them; at 16
    held of one chip of 16 490.3 M = 5.88 / 7.84 GB."""
    def attention(heads):
        return 2 * D * heads * HD + 2 * D * HKV * HD + D * heads + 2 * HD

    assert 2 * D * 48 * HD + 2 * D * HKV * HD == 29360128       # "29.36 M"
    assert 2 * D * 64 * HD + 2 * D * HKV * HD == 37748736       # "37.75 M"
    expert = 3 * D * F
    assert expert == 3145728                                    # "3.146 M"

    def sparse(heads, held):
        return (attention(heads) + D * 256 + (held + 1) * expert + 2 * D)

    def total(held):
        return (2 * V * D + attention(48) + 3 * D * 8192 + 2 * D
                + 3 * sparse(64, held) + sparse(48, held) + D)

    assert total(32) == 691625216
    assert round(12 * total(32) / 1e9, 2) == 8.30
    assert round(16 * total(32) / 1e9, 2) == 11.07
    assert total(16) == 490298624
    assert round(12 * total(16) / 1e9, 2) == 5.88
    assert round(16 * total(16) / 1e9, 2) == 7.84


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (
        "laguna-xs.2", "b1-len16384-host", 1, None, 1, T, "host", 4)
    assert len(cell["why"]) <= 200 and "2:3" in cell["why"]
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert "laguna-16k" in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == "laguna-16k"] == [{
        "name": "laguna-16k", "config": "laguna-xs.2",
        "traffic": "b1-len16384-host", "chips": 1, "why": cell["why"]}]
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": T, "unit": "tokens/s"}}
    # `mellum2-16k`'s and `qwen3next-16k`'s traffic, key for key
    for other in ("mellum2-16k", "qwen3next-16k"):
        theirs, _, _ = bench_run.load_cell(other, (BENCH,))
        assert {k: v for k, v in theirs.items()
                if k not in ("config", "why", "name")} == {
            k: v for k, v in cell.items()
            if k not in ("config", "why", "name")}


def test_train_flops_equal_the_issues_table_from_the_shapes():
    cell, config, family = real()
    assert family.score_pairs(T) == CAUSAL
    assert family.score_pairs(T, W) == BAND
    want = {
        "projections": sum(2 * (2 * D * h * HD + 2 * D * HKV * HD)
                           for h in HEADS),
        "full_attention": 2 * (2 * 2 * 48 * HD * CAUSAL / T),
        "sliding_attention": 3 * (2 * 2 * 64 * HD * BAND / T),
        "gates": sum(2 * D * h for h in HEADS),
        "dense_ffn": 3 * 2 * D * 8192,
        "shared_experts": 4 * 3 * 2 * D * F,
        "router": 4 * 2 * D * 256,
        "experts": 4 * 1 * 3 * 2 * D * F,
        "head": 2 * D * V}
    got = family.forward_flops_per_token(config, T)
    assert got == pytest.approx(want)
    tera = {k: round(v * T / 1e12, 2) for k, v in got.items()}
    assert tera == {"projections": 5.63, "full_attention": 6.60,
                    "sliding_attention": 0.81, "gates": 0.02,
                    "dense_ffn": 1.65, "shared_experts": 0.41,
                    "router": 0.07, "experts": 0.41, "head": 0.84}
    total = sum(got.values())
    assert round(total * T / 1e12, 2) == 16.45
    assert family.train_flops(config, cell) == pytest.approx(3 * total * T)
    assert round(family.train_flops(config, cell) / 1e12, 1) == 49.3
    share = {k: 100 * v / total for k, v in got.items()}
    assert round(share["projections"] + share["full_attention"]
                 + share["sliding_attention"] + share["gates"]) == 79
    assert round(share["experts"], 1) == 2.5
    # 16 held of one chip of 16: the other side of the share rule
    half = dict(config, num_experts=16, expert_parallel_size=16)
    assert round(family.train_flops(half, cell) / 1e12, 1) == 48.7


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    assert counts.heads_of(config, "sliding_attention") == [64, 64, 64]
    assert counts.heads_of(config, "full_attention") == [48, 48]
    assert counts.window_pairs(config, cell) == BAND
    flops, nbytes = counts.flash_window_cost(config, cell)
    assert flops == 3 * 14 * 64 * BAND * HD
    assert nbytes == 3 * 6 * T * (64 * HD + HKV * HD) * 2
    flops, nbytes = counts.flash_grouped_cost(config, cell)
    assert flops == 2 * 14 * 48 * CAUSAL * HD
    assert nbytes == 2 * 6 * T * (48 * HD + HKV * HD) * 2
    assert counts.sparse_layers(config) == 4
    flops, nbytes = counts.expert_matmul_cost(config, cell, 16384.0)
    assert flops == 4 * 9 * 2 * 16384 * D * F
    assert nbytes == 4 * 9 * 2 * (16384 * D + 16384 * F + 32 * D * F)


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
        family.make_batch(config, dict(cell, length=8192),
                          np.random.default_rng(0))


def test_new_readers_match_benchmark_json_and_read_none_without_a_trace():
    listed = {m["name"]: m for m in benchmark_json()["per_layer"]}
    assert set(NEW_READERS) <= set(listed)
    cell, config, _ = real()
    no_trace = {"cell": cell, "config": config, "trace": None, "steps": 5}
    for name, source in NEW_READERS.items():
        module = reader(name)
        assert module.META["cells"] == ["laguna-16k"] == listed[name][
            "workloads"]
        assert module.META["moves"] == "mfu" == listed[name]["moves"]
        assert module.META["unit"] == listed[name]["unit"]
        assert module.META["layer"] == listed[name]["layer"]
        assert module.META["source"] == source == listed[name]["source"]
        if source == "device_trace":
            assert module.compute(no_trace) is None
    readers = bench_run.layer_readers("laguna-16k", (BENCH,))
    everywhere = {m["name"] for m in benchmark_json()["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert "laguna-16k" in readers[name].META["cells"]
    assert not set(NEW_READERS) & set(
        bench_run.layer_readers("mellum2-16k", (BENCH,)))


def rows_fixture():
    """Rows as `observe/trace.op_rows` gives them for 2 traced steps."""
    def row(instruction, bucket, self_s, scope="", op_type=None,
            kernel=None, flops=0.0):
        return {"module": "jit_step(1)", "instruction": instruction,
                "bucket": bucket, "self_s": self_s, "calls": 2,
                "op_type": op_type, "name_scope": scope, "op_name": "",
                "phase": "backward", "flops": flops, "kernel": kernel,
                "joined": True}

    return [
        row("fusion.1", "matmul", 0.050, "sliding_attention", "mul", None,
            3e9),
        row("fusion.2", "elementwise", 0.004,
            "checkpoint/sliding_attention/attention_head_gate", "sigmoid"),
        row("custom-call.1", "custom_call", 0.010, "sliding_attention",
            "flash_attention", "flash_window_fwd"),
        row("custom-call.2", "custom_call", 0.020, "sliding_attention",
            "flash_attention", "flash_window_dkv"),
        row("fusion.3", "matmul", 0.040, "full_attention", "mul", None, 2e9),
        row("fusion.4", "elementwise", 0.002,
            "full_attention/attention_head_gate", "elementwise_mul"),
        row("custom-call.3", "custom_call", 0.100, "full_attention",
            "flash_attention", "flash_fwd"),
        row("custom-call.4", "custom_call", 0.140, "full_attention",
            "flash_attention", "flash_dkv"),
        row("fusion.5", "matmul", 0.030, "", "moe_dropless", None, 1e9),
        row("custom-call.5", "custom_call", 0.024, "", "moe_dropless",
            "ragged_dot"),
        row("fusion.6", "matmul", 0.008, "shared_expert", "mul"),
        row("fusion.7", "elementwise", 0.005, "", "adam"),
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
    import kernel_counts_lfm2

    assert reader("device_ms_per_step.sliding_attention_64h").compute(
        traced) == pytest.approx((50 + 4 + 10 + 20) / 2)
    assert reader("device_ms_per_step.full_attention_48h").compute(
        traced) == pytest.approx((40 + 2 + 100 + 140) / 2)
    assert reader("device_ms_per_step.attention_head_gate").compute(
        traced) == pytest.approx((4 + 2) / 2)
    # the expert op's rows and its kernels; not the shared expert
    assert reader("device_ms_per_step.routed_ffn_256").compute(
        traced) == pytest.approx((30 + 24) / 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    cell, config = traced["cell"], traced["config"]
    for name, cost, ms in (
            ("flash_window512_roofline_share", counts.flash_window_cost,
             15.0),
            ("flash_grouped_48h_roofline_share", counts.flash_grouped_cost,
             120.0)):
        flops, nbytes = cost(config, cell)
        want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / ms
        assert reader(name).compute(traced) == pytest.approx(want)
        assert 0 < want < 100
    # the held rows come from the device-side counters
    monkeypatch.setattr(kernel_counts_lfm2, "held_row_share", lambda: 0.125)
    assert reader("held_expert_row_share_256").compute(traced) == 12.5
    rows = 0.125 * T * 8
    flops, nbytes = counts.expert_matmul_cost(config, cell, rows)
    want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / 12.0
    assert reader("expert_matmul_w512_roofline_share").compute(
        traced) == pytest.approx(want)
    assert 0 < want < 100
    monkeypatch.setattr(kernel_counts_lfm2, "held_row_share", lambda: None)
    assert reader("held_expert_row_share_256").compute(traced) is None
    assert reader("expert_matmul_w512_roofline_share").compute(
        traced) is None
    # a program whose rows carry no name scope (the parent's) reads
    # nothing; one without the window kernels no share

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture() if not (r["kernel"] or "").startswith(
                    "flash_window")]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    for name in ("device_ms_per_step.sliding_attention_64h",
                 "device_ms_per_step.full_attention_48h",
                 "device_ms_per_step.attention_head_gate",
                 "flash_window512_roofline_share"):
        assert reader(name).compute(traced) is None, name


def test_the_fill_share_reads_the_programs_two_counters(monkeypatch):
    from paddle_tpu.observe.monitoring import runtime_stats

    fill = reader("flash_window512_fill_share")
    for field in ("flash_window_calls", "flash_window_pairs_allowed",
                  "flash_window_entries_computed"):
        monkeypatch.setattr(runtime_stats, field, 0)
    assert fill.compute({}) is None               # no window call traced
    runtime_stats.record_flash_window_call(BAND, 63 * 512 * 512)
    runtime_stats.record_flash_window_call(BAND, 63 * 512 * 512)
    assert fill.compute({}) == pytest.approx(100 * BAND / (63 * 512 * 512))
    assert 49.9 < fill.compute({}) < 50.1
    # a program from before the counters: nothing to read, no raise
    monkeypatch.setattr(type(runtime_stats), "snapshot", lambda self: {})
    assert fill.compute({}) is None


def test_toy_laguna_cell_runs_the_harness(capfd):
    from paddle_tpu.observe.monitoring import runtime_stats

    before = runtime_stats.snapshot()
    result = bench_run.run_cell("tiny-laguna-host", 2**31 + 11, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out
    assert set(result["metrics"]) >= {"dispatch_ms.train",
                                      "compiles_in_window"}
    out = capfd.readouterr().out
    assert '"loss_fell": true' in out
    took = runtime_stats.delta(before)
    assert (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"]) == (5, 0)
    assert took["flash_window_calls"] > 0 and took["flash_grouped_calls"] > 0
    assert took["flash_window_entries_computed"] \
        >= took["flash_window_pairs_allowed"] > 0
    assert took["attention_head_gate_calls"] == 5


def test_parity_script_compares_logits_routing_and_every_leaf():
    parity = load("laguna_parity")
    _, config, family = bench_run.load_cell("tiny-laguna-host",
                                            (BENCH, FIXTURES))
    parity.LAST, parity.Q_BLOCK, parity.GRAD_Q_BLOCK = 16, 8, 8
    r = parity.check_seed(config, family, 2**31 + 3)
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_loss"]
    assert checks["f32_routing"] and checks["f32_held_counts"]
    assert checks["grads_are_compared"] and checks["f32_grads"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-4
    assert len(r["f32"]["held_rows"]) == 4          # the sparse layers
