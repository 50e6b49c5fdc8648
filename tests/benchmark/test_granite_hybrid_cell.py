"""The `granite-4.0-h-micro` configuration, its family, the
`granite4h-8k` cell and the readers PR 58 adds, on the CPU: the files
and BENCHMARK.json agree (entries looked up BY NAME, never by position:
the next cell is appended after this one), the configuration holds the
catalog's numbers and exactly its three cuts, the family's map onto the
builder, the parameters, `train_flops` and the kernel counts against
hand counts, each reader on a fixture and without a trace, the parity
script's arithmetic at a toy size, and a toy cell through `run_cell`.
No number from here is a speed.
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
import kernel_counts_granite_hybrid as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
CELL, CONFIG = "granite4h-8k", "granite-4.0-h-micro"
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/"
          "main/config.json")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CATALOG = {      # the catalog row's `config`, granite-4.0-h-micro
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
NEW_READERS = {
    "device_ms_per_step.state_space_duality": "device_trace",
    "device_ms_per_step.full_attention_d64": "device_trace",
    "ssd_scan_roofline_share": "device_trace",
    "flash_gqa_scaled_roofline_share": "device_trace",
    "ssd_scan_chunks_per_step": "program_counter",
    "ssd_scans_xla_per_step": "program_counter",
    "device_ms_per_step.short_conv_w4352": "device_trace",
    "short_conv_w4352_roofline_share": "device_trace"}
T, D, H, HKV, HD, F, V = 8192, 2048, 32, 8, 64, 8192, 12544
SH, P, S, DI, XBC = 64, 64, 128, 4096, 4352


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


def by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_configuration_holds_the_published_numbers_and_exactly_its_cuts():
    _, config, _ = real()
    differs = [k for k, v in CATALOG.items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(REDUCED) == sorted(config["reduced"])
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, V)
    assert config["layer_types"] == PERIOD == CATALOG["layer_types"][:10]
    assert V * 8 == 100352 and V == 98 * 128    # the plain eighth: the floor
    published = config["published"]
    assert (published["vocab_size"], published["num_hidden_layers"]) == (
        100352, 40)
    assert config["sequence_length"] == T
    # every published width and the four multipliers
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["shared_intermediate_size"],
            config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_n_groups"],
            config["mamba_d_conv"], config["mamba_chunk_size"]) == (
        D, H, HKV, F, SH, P, S, 1, 4, 256)
    assert (config["embedding_multiplier"], config["residual_multiplier"],
            config["attention_multiplier"], config["logits_scaling"]) == (
        12, 0.22, 0.015625, 8)
    assert config["attention_multiplier"] == 1 / 64 != HD ** -0.5
    entry = by_name(benchmark_json()["configs"], CONFIG)
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/granite-4.0-h-micro.json"
    assert len(entry["why"]) <= 200
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["recompute"], t["use_amp"],
            t["initializer_range"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, "layer", True, 0.02)
    assert {"mamba", "initializer_range", "attention", "multipliers", "mlp",
            "norm", "weights", "training", "sequence_length",
            "recomputation"} <= set(config["assumed"])
    assert "8 chips share the vocabulary" in config["deployment"]
    assert "four pipeline stages" in config["deployment"]
    assert "772,160,448" in config["reduced_why"]
    assert "float32" in config["precision"]


def test_the_family_maps_the_published_keys_onto_the_builder():
    _, config, family = real()
    args = family.architecture(config)
    assert (args["positions"], args["qk_norm"]) == ("none", None)
    assert args["layer_types"] == ["mamba"] * 5 + ["full_attention"] \
        + ["mamba"] * 4
    assert args["intermediate_size"] == F
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "rms_norm_eps", "tie_word_embeddings", "mamba_n_heads",
                "mamba_d_head", "mamba_d_state", "mamba_n_groups",
                "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
                "embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling"):
        assert args[key] == CATALOG[key], key
    assert args["num_dense_layers"] == args["num_hidden_layers"] == 10
    assert (args["num_experts"], args["num_experts_per_tok"]) == (0, 0)
    assert "mamba_dt_rank" not in args
    assert not {"model_type", "hidden_act", "normalization_function",
                "position_embedding_type", "mamba_conv_bias",
                "mamba_proj_bias", "num_local_experts", "rope_theta",
                "rope_scaling", "shared_intermediate_size",
                "max_position_embeddings"} & set(args)
    import inspect

    from paddle_tpu.models import decoder

    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    assert set(config["training"]) <= (
        set(inspect.signature(decoder.build_model).parameters)
        | set(inspect.signature(decoder.decoder).parameters))
    for key, value in (("num_local_experts", 64), ("hidden_act", "gelu"),
                       ("normalization_function", "layernorm"),
                       ("position_embedding_type", "rope"),
                       ("attention_bias", True), ("mamba_n_groups", 8),
                       ("mamba_proj_bias", True), ("mamba_conv_bias", False)):
        with pytest.raises(NotImplementedError, match=key):
            family.architecture(dict(config, **{key: value}))
    with pytest.raises(NotImplementedError, match="layer types"):
        family.architecture(dict(config, layer_types=["mamba", "moe"]))
    # no model's name in the program
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    text = f.read().lower()
                assert "granite" not in text, name


def test_parameters_by_hand():
    """772.2 M parameters: 9.27 GB of float32 master weights and two
    Adam moments (ISSUE 58's table)."""
    mixer = D * (DI + XBC + SH) + XBC * 4 + XBC + 3 * SH + DI + DI * D
    assert D * (DI + XBC + SH) == 17432576 and mixer == 25847232
    mlp = D * 2 * F + F * D
    assert mlp == 50331648
    attention = 2 * D * D + 2 * D * HKV * HD
    assert attention == 10485760
    mamba_layer, attention_layer = (mixer + mlp + 2 * D,
                                    attention + mlp + 2 * D)
    assert (mamba_layer, attention_layer) == (76182976, 60821504)
    total = 9 * mamba_layer + attention_layer + D + V * D
    assert V * D == 25690112 and total == 772160448
    assert round(12 * total / 1e9, 2) == 9.27
    assert round(16 * total / 1e9, 2) == 12.35


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (
        CONFIG, "b1-len8192-host", 1, None, 1, T, "host", 4)
    assert len(cell["why"]) <= 200
    bj = benchmark_json()
    tokens = by_name(bj["end_to_end"], "tokens_per_s")
    assert CELL in tokens["workloads"]
    assert by_name(bj["workloads"], CELL) == {
        "name": CELL, "config": CONFIG, "traffic": "b1-len8192-host",
        "chips": 1, "why": cell["why"]}
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": T, "unit": "tokens/s"}}
    # `phi4flash-8k`'s traffic, key for key
    theirs, _, _ = bench_run.load_cell("phi4flash-8k", (BENCH,))
    assert {k: v for k, v in theirs.items()
            if k not in ("config", "why", "name")} == {
        k: v for k, v in cell.items() if k not in ("config", "why", "name")}


def test_train_flops_equal_the_issues_table_from_the_shapes():
    cell, config, family = real()
    want = {
        "state_space_projections": 9 * 2 * (D * (DI + XBC + SH) + DI * D),
        "state_space_recurrence": 9 * 4 * S * P * SH,
        "attention_projections": 2 * (2 * D * D + 2 * D * HKV * HD),
        "full_attention": 2 * H * 2 * HD * (T * (T + 1) // 2) / T,
        "mlp": 10 * 3 * 2 * D * F,
        "head": 2 * D * V}
    got = family.forward_flops_per_token(config, T)
    assert got == pytest.approx(want)
    mega = {k: round(v / 1e6, 1) for k, v in got.items()}
    assert mega == {"state_space_projections": 464.8,
                    "state_space_recurrence": 18.9,
                    "attention_projections": 21.0, "full_attention": 33.6,
                    "mlp": 1006.6, "head": 51.4}
    total = sum(got.values())
    assert round(total / 1e6, 1) == 1596.2
    assert family.train_flops(config, cell) == pytest.approx(3 * total * T)
    assert round(family.train_flops(config, cell) / 1e12, 2) == 39.23
    assert round(100 * got["mlp"] / total, 1) == 63.1
    assert round(100 * got["head"] / total, 1) == 3.2


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    assert counts.scan_layers(config) == 9
    flops, nbytes = counts.ssd_scan_cost(config, cell)
    # the sequential form: 4 N P H a token a layer forward, twice that
    # backward
    assert flops == 9 * 3 * 4 * S * P * SH * T
    assert 4 * S * P * SH == 2097152
    wide, narrow, step = T * DI * 2, T * S * 2, T * SH * 4
    entry = (T // 256) * SH * P * S * 4
    assert entry == wide == 67108864
    assert nbytes == 9 * ((2 + 4) * wide + (2 + 4) * narrow + 3 * step
                          + 2 * entry + 2 * SH * 4)
    # the mathematics' bytes take longer than its FLOP at the peaks:
    # the share is reckoned by bytes, and the kernels' time holds twice
    # the FLOP and the masks' vector work
    assert nbytes / 819e9 > flops / 197e12
    # the joint convolution: no FLOP, xBC in and out forward, xBC, dy
    # in and dxBC out backward, bfloat16
    assert counts.short_conv_cost(config, cell) == (
        0.0, 9 * 5 * T * XBC * 2)
    assert XBC == DI + 2 * S == 34 * 128
    theirs, lfm2, _ = bench_run.load_cell("lfm2-8k", (BENCH,))
    import kernel_counts_lfm2

    flops, nbytes = counts.flash_gqa_scaled_cost(config, cell)
    assert flops == 7.0 * H * T * T * HD
    assert nbytes == 6.0 * T * (D + HKV * HD) * 2
    # `lfm2-8k`'s geometry: its count a layer
    a_layer = tuple(v / kernel_counts_lfm2.layers_of(lfm2, "full_attention")
                    for v in kernel_counts_lfm2.flash_gqa_cost(lfm2, theirs))
    assert (flops, nbytes) == a_layer
    assert counts.SSD_KERNELS == ("ssd_scan",)


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
        assert listed[name]["better"] == (
            "higher" if name.endswith("roofline_share") else "lower")
        if source == "device_trace":
            assert module.compute(no_trace) is None
    readers = bench_run.layer_readers(CELL, (BENCH,))
    everywhere = {m["name"] for m in benchmark_json()["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert CELL in readers[name].META["cells"]
    assert not set(NEW_READERS) & set(
        bench_run.layer_readers("phi4flash-8k", (BENCH,)))


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
        row("fusion.1", "matmul", 0.060, "state_space_duality", "mul", None,
            3e9),
        row("custom-call.1", "custom_call", 0.014, "state_space_duality",
            "ssd_scan", "ssd_scan_fwd"),
        row("custom-call.2", "custom_call", 0.034,
            "checkpoint/state_space_duality", "ssd_scan", "ssd_scan_bwd"),
        row("custom-call.3", "custom_call", 0.004, "state_space_duality",
            "short_conv", "short_conv_fwd"),
        row("custom-call.6", "custom_call", 0.008,
            "checkpoint/state_space_duality", "short_conv",
            "short_conv_bwd"),
        row("fusion.2", "elementwise", 0.006,
            "state_space_duality/gated_rms_norm", "gated_rms_norm"),
        row("fusion.3", "matmul", 0.004, "full_attention", "mul"),
        row("custom-call.4", "custom_call", 0.006, "full_attention",
            "flash_attention", "flash_gqa_fwd"),
        row("custom-call.5", "custom_call", 0.014, "full_attention",
            "flash_attention", "flash_gqa_dkv"),
        row("fusion.6", "matmul", 0.300, "", "mul"),
        row("fusion.7", "elementwise", 0.010, "", "adam"),
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
    assert reader("device_ms_per_step.state_space_duality").compute(
        traced) == pytest.approx((60 + 14 + 34 + 4 + 8 + 6) / 2)
    assert reader("device_ms_per_step.full_attention_d64").compute(
        traced) == pytest.approx((4 + 6 + 14) / 2)
    assert reader("device_ms_per_step.short_conv_w4352").compute(
        traced) == pytest.approx((4 + 8) / 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    cell, config = traced["cell"], traced["config"]
    for name, cost, ms in (
            ("ssd_scan_roofline_share", counts.ssd_scan_cost, 24.0),
            ("short_conv_w4352_roofline_share", counts.short_conv_cost, 6.0),
            ("flash_gqa_scaled_roofline_share", counts.flash_gqa_scaled_cost,
             10.0)):
        flops, nbytes = cost(config, cell)
        want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / ms
        assert reader(name).compute(traced) == pytest.approx(want)
        assert 0 < want < 100
    # a program whose rows carry no name scope, and none of the scan's
    # kernels (the parent's), reads nothing

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture() if not (r["kernel"] or "").startswith(
                    "ssd_scan")]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    for name in ("device_ms_per_step.state_space_duality",
                 "device_ms_per_step.full_attention_d64",
                 "ssd_scan_roofline_share"):
        assert reader(name).compute(traced) is None, name


def test_the_counter_readers_read_the_programs_counters(monkeypatch):
    from paddle_tpu.observe.monitoring import runtime_stats

    chunks = reader("ssd_scan_chunks_per_step")
    on_xla = reader("ssd_scans_xla_per_step")
    for field in ("ssd_scans_kernel", "ssd_scans_xla", "ssd_scan_chunks"):
        monkeypatch.setattr(runtime_stats, field, 0)
    assert chunks.compute({}) is None           # no kernel call traced
    assert on_xla.compute({}) == 0              # and none on XLA: a number
    for _ in range(27):
        runtime_stats.record_ssd_scan(True, 32)
    runtime_stats.record_ssd_scan(False, 0)
    assert chunks.compute({}) == 864 and on_xla.compute({}) == 1
    # a program from before the counters: nothing to read, no raise
    monkeypatch.setattr(type(runtime_stats), "snapshot", lambda self: {})
    assert chunks.compute({}) is None and on_xla.compute({}) is None


def test_toy_granite_hybrid_cell_runs_the_harness(capfd):
    from paddle_tpu.observe.monitoring import runtime_stats

    before = runtime_stats.snapshot()
    result = bench_run.run_cell("tiny-granite-hybrid-host", 2**31 + 11, 1.0,
                                True, roots=(BENCH, FIXTURES),
                                device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out;
    # the toy's heads of 16 are not the kernels': its scans ran on XLA
    assert set(result["metrics"]) >= {"dispatch_ms.train",
                                      "compiles_in_window"}
    out = capfd.readouterr().out
    assert '"loss_fell": true' in out
    took = runtime_stats.delta(before)
    assert took["ssd_scans_xla"] > 0 and took["ssd_scans_kernel"] == 0
    assert took["scaled_attention_calls"] == 1
    assert took["gated_rms_norm_calls"] >= 2
    assert took["short_conv_bias_calls"] > 0
    assert took["selective_scans_xla"] == 0


def test_parity_script_compares_logits_and_every_leaf():
    parity = load("granite_hybrid_parity")
    _, config, family = bench_run.load_cell("tiny-granite-hybrid-host",
                                            (BENCH, FIXTURES))
    config["training"]["initializer_range"] = 0.02
    parity.LAST, parity.Q_BLOCK, parity.TIME_BLOCK = 16, 8, 8
    parity.STAND_INS = parity.STAND_INS[:1]     # one of three: seconds
    r = parity.check_seed(config, family, 2**31 + 3, stand_ins=True)
    checks = r["checks"]
    # the toy's heads of 16 are no kernel's: `scan_xla` on both sides,
    # and the check that is the KERNELS' says it held nothing
    scan = r["bf16_scan_against_xla"]
    assert set(scan) == {"kernels", "y", *parity.SCAN_GRADS}
    assert scan["kernels"] is False and scan["ddt"] == scan["da"] == 0.0
    assert not checks["bf16_scan_kernels_against_xla"]
    # between the kernels' reading of the step's gradient and the first
    # draft's; the rate's gradient, a sum that cancels, is not held
    assert 1.1e-3 < parity.SCAN_LIMIT < 1.4e-2
    assert set(parity.SCAN_GRADS) - set(parity.SCAN_HELD) == {"da"}
    assert checks["f32_logits"] and checks["f32_loss"] and checks["f32_grads"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-3, r["f32"]
    names = set(r["f32"]["grad_err"])
    assert {"layer0.a_log", "layer0.dt_bias", "layer0.d",
            "layer0.gate_norm_w", "layer0.w_z", "layer0.w_xbc",
            "layer0.w_dt", "layer1.wq", "layer2.a_log"} <= names
    assert set(r["lowered"]) == {"bf16_scan_state",
                                 "attention_scale_an_eighth"}
    assert [key for _, key in load("granite_hybrid_parity").STAND_INS] == [
        "state_dtype", "decay_dtype", "norm_dtype"]
