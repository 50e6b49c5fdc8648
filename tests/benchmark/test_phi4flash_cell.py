"""The `phi-4-mini-flash-reasoning` configuration, its family, the
`phi4flash-8k` cell and the readers PR 53 adds, on the CPU: the files
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
import kernel_counts_phi4flash as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")
CATALOG = {      # the catalog row's `config`, Phi-4-mini-flash-reasoning
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
KINDS = ["mamba", "sliding_attention", "mamba", "full_attention",
         "gated_memory", "cross_attention"]
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
NEW_READERS = {
    "device_ms_per_step.state_space": "device_trace",
    "device_ms_per_step.gated_memory": "device_trace",
    "device_ms_per_step.differential_attention": "device_trace",
    "device_ms_per_step.cross_attention": "device_trace",
    "selective_scan_roofline_share": "device_trace",
    "flash_diff_roofline_share": "device_trace",
    "selective_scan_chunks_per_step": "program_counter",
    "selective_scans_xla_per_step": "program_counter"}
T, D, H, HKV, HD, W, F, V = 8192, 2560, 40, 20, 64, 512, 10240, 25088
DI, S, R = 5120, 16, 160
CAUSAL, BAND = 33558528, 4063488


def real():
    return bench_run.load_cell("phi4flash-8k", (BENCH,))


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
    assert sorted(differs + ["layer_types"]) == sorted(REDUCED) \
        == sorted(config["reduced"])
    assert (config["num_hidden_layers"], config["vocab_size"]) == (6, V)
    assert config["layer_types"] == KINDS
    assert config["layer_indices"] == [14, 15, 16, 17, 18, 19]
    assert (config["shared_memory_layer"], config["shared_kv_layer"]) == (2, 3)
    # the published class's defaults, at the published hidden size
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) == (
        S, 4, 2, -(-D // 16))
    assert V == 196 * 128 == -(-200064 // (8 * 128)) * 128
    assert V >= 200064 // 8             # the floor: an eighth
    published = config["published"]
    assert (published["vocab_size"], published["num_hidden_layers"]) == (
        200064, 32)
    assert config["sequence_length"] == T
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "sliding_window", "layer_norm_eps"):
        assert config[key] == CATALOG[key], key        # no width is cut
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == "phi-4-mini-flash-reasoning"]
    assert len(entry) == 1
    entry = entry[0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/phi-4-mini-flash-reasoning.json"
    assert len(entry["why"]) <= 200
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["recompute"], t["use_amp"],
            t["initializer_range"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, "layer", True, 0.02)
    assert {"mamba", "layer rule", "gated memory unit",
            "differential attention", "attention_bias", "positions", "norm",
            "mlp", "window edge", "weights", "training", "sequence_length",
            "recomputation"} <= set(config["assumed"])
    assert "8 chips share the vocabulary" in config["deployment"]


def test_the_family_maps_the_published_keys_onto_the_builder():
    _, config, family = real()
    args = family.architecture(config)
    assert (args["norm"], args["attention"], args["positions"],
            args["qk_norm"]) == ("layer_norm", "differential", "none", None)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "sliding_window", "layer_norm_eps",
                "tie_word_embeddings"):
        assert args[key] == CATALOG[key], key
    assert args["num_dense_layers"] == args["num_hidden_layers"] == 6
    assert (args["num_experts"], args["num_experts_per_tok"]) == (0, 0)
    assert not {"model_type", "hidden_act", "mlp_bias", "lm_head_bias",
                "embd_pdrop", "resid_pdrop", "mb_per_layer",
                "max_position_embeddings"} & set(args)
    import inspect

    from paddle_tpu.models import decoder

    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    assert set(config["training"]) <= (
        set(inspect.signature(decoder.build_model).parameters)
        | set(inspect.signature(decoder.decoder).parameters))
    for key, value in (("hidden_act", "gelu"), ("mlp_bias", True),
                       ("lm_head_bias", True), ("resid_pdrop", 0.1),
                       ("mb_per_layer", 3)):
        with pytest.raises(NotImplementedError, match=key):
            family.architecture(dict(config, **{key: value}))
    # no model's name in the program
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    text = f.read().lower()
                assert "phi4" not in text and "phi-4" not in text \
                    and "sambay" not in text, name


def test_parameters_by_hand():
    """697.3 M parameters: 8.37 GB of float32 master weights and two
    Adam moments, 11.16 GB with a float32 gradient beside them."""
    mlp = 3 * D * F
    norms = 2 * 2 * D
    mamba = (D * 2 * DI + (4 + 1) * DI + DI * (R + 2 * S) + R * DI + DI
             + DI * S + DI + DI * D)
    assert mamba == 41241600 and mlp == 78643200
    own = D * (D + 2 * HKV * HD) + (D + 2 * HKV * HD) + D * D + D \
        + 4 * HD + 2 * HD
    assert own == 19668864
    cross = D * D + D + D * D + D + 4 * HD + 2 * HD
    memory = 2 * D * DI
    layers = [mamba, own, mamba, own, memory, cross]
    assert memory + mlp + norms == 104867840
    assert cross + mlp + norms == 91766144
    total = sum(layers) + 6 * (mlp + norms) + 2 * D + V * D
    assert total == 697299072
    assert round(12 * total / 1e9, 2) == 8.37
    assert round(16 * total / 1e9, 2) == 11.16


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (
        "phi-4-mini-flash-reasoning", "b1-len8192-host", 1, None, 1, T,
        "host", 4)
    assert len(cell["why"]) <= 200
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert "phi4flash-8k" in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == "phi4flash-8k"] == [{
        "name": "phi4flash-8k", "config": "phi-4-mini-flash-reasoning",
        "traffic": "b1-len8192-host", "chips": 1, "why": cell["why"]}]
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": T, "unit": "tokens/s"}}
    # `lfm2-8k`'s and `joyai-8k`'s traffic, key for key, but for the
    # pool (4 host batches, as the 16k cells: ISSUE 53 gives it)
    for other in ("lfm2-8k", "joyai-8k"):
        theirs, _, _ = bench_run.load_cell(other, (BENCH,))
        assert {k: v for k, v in theirs.items()
                if k not in ("config", "why", "name", "pool")} == {
            k: v for k, v in cell.items()
            if k not in ("config", "why", "name", "pool")}


def test_train_flops_equal_the_issues_table_from_the_shapes():
    cell, config, family = real()
    assert family.score_pairs(T) == CAUSAL
    assert family.score_pairs(T, W) == BAND
    want = {
        "state_space_projections": 2 * 2 * (
            D * 2 * DI + DI * (R + 2 * S) + R * DI + DI * D),
        "gated_memory": 2 * 2 * D * DI,
        "attention_projections": 2 * (3 * 2 * D * D + 2 * 2 * D * HKV * HD),
        "sliding_attention": 2 * H * (HD + 2 * HD) * BAND / T,
        "full_attention": 2 * H * (HD + 2 * HD) * CAUSAL / T,
        "cross_attention": 2 * H * (HD + 2 * HD) * CAUSAL / T,
        "mlp": 6 * 3 * 2 * D * F,
        "head": 2 * D * V}
    got = family.forward_flops_per_token(config, T)
    assert got == pytest.approx(want)
    tera = {k: round(v * T / 1e12, 2) for k, v in got.items()}
    assert tera == {"state_space_projections": 1.35, "gated_memory": 0.43,
                    "attention_projections": 0.86, "sliding_attention": 0.06,
                    "full_attention": 0.52, "cross_attention": 0.52,
                    "mlp": 7.73, "head": 1.05}
    total = sum(got.values())
    assert round(total * T / 1e12, 2) == 12.51
    assert family.train_flops(config, cell) == pytest.approx(3 * total * T)
    assert round(family.train_flops(config, cell) / 1e12, 1) == 37.5
    assert round(100 * got["mlp"] / total) == 62


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    assert counts.channels(config) == DI and counts.scan_layers(config) == 2
    flops, nbytes = counts.selective_scan_cost(config, cell)
    wide, narrow = T * DI * 2, T * S * 2
    entry = (T // 256) * DI * S * 4
    assert flops == 0.0
    assert nbytes == 2 * ((3 + 5) * wide + (2 + 4) * narrow + 2 * entry
                          + DI * S * 4 + 2 * DI * 4)
    assert round(nbytes / 1e9, 2) == 1.39
    assert counts.pairs_of(config, cell, "sliding_attention") == BAND
    assert counts.pairs_of(config, cell, "cross_attention") == CAUSAL
    flops, nbytes = counts.flash_diff_cost(config, cell)
    assert flops == 2 * H * 10 * HD * (BAND + 2 * CAUSAL)
    assert nbytes == 3 * T * 2 * (3 * H * HD + 3 * H * 2 * HD + 6 * HKV * HD)
    # the mathematics is MXU-bound at these lengths: FLOP over the peak
    assert flops / 197e12 > nbytes / 819e9


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
        assert module.META["cells"] == ["phi4flash-8k"] == listed[name][
            "workloads"]
        assert module.META["moves"] == "mfu" == listed[name]["moves"]
        assert module.META["unit"] == listed[name]["unit"]
        assert module.META["layer"] == listed[name]["layer"]
        assert module.META["source"] == source == listed[name]["source"]
        if source == "device_trace":
            assert module.compute(no_trace) is None
    readers = bench_run.layer_readers("phi4flash-8k", (BENCH,))
    everywhere = {m["name"] for m in benchmark_json()["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert "phi4flash-8k" in readers[name].META["cells"]
    assert not set(NEW_READERS) & set(
        bench_run.layer_readers("laguna-16k", (BENCH,)))


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
        row("fusion.1", "matmul", 0.030, "state_space", "mul", None, 3e9),
        row("custom-call.1", "custom_call", 0.006, "state_space",
            "selective_scan", "selective_scan_fwd"),
        row("custom-call.2", "custom_call", 0.014, "checkpoint/state_space",
            "selective_scan", "selective_scan_bwd"),
        row("custom-call.3", "custom_call", 0.002, "state_space",
            "short_conv", "short_conv_fwd"),
        row("fusion.2", "matmul", 0.008, "gated_memory", "mul"),
        row("fusion.3", "matmul", 0.012,
            "differential_attention/sliding_attention", "mul"),
        row("custom-call.4", "custom_call", 0.004,
            "differential_attention/sliding_attention", "flash_attention",
            "flash_window_fwd"),
        row("custom-call.5", "custom_call", 0.016,
            "differential_attention/full_attention", "flash_attention",
            "flash_dkv"),
        row("fusion.4", "elementwise", 0.002,
            "differential_attention/full_attention/diff_combine",
            "diff_combine"),
        row("custom-call.6", "custom_call", 0.020, "cross_attention",
            "flash_attention", "flash_fwd"),
        row("fusion.5", "matmul", 0.010, "cross_attention", "mul"),
        row("fusion.6", "matmul", 0.100, "", "mul"),
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
    assert reader("device_ms_per_step.state_space").compute(
        traced) == pytest.approx((30 + 6 + 14 + 2) / 2)
    assert reader("device_ms_per_step.gated_memory").compute(
        traced) == pytest.approx(8 / 2)
    assert reader("device_ms_per_step.differential_attention").compute(
        traced) == pytest.approx((12 + 4 + 16 + 2) / 2)
    assert reader("device_ms_per_step.cross_attention").compute(
        traced) == pytest.approx((20 + 10) / 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    cell, config = traced["cell"], traced["config"]
    for name, cost, ms in (
            ("selective_scan_roofline_share", counts.selective_scan_cost,
             10.0),
            ("flash_diff_roofline_share", counts.flash_diff_cost, 20.0)):
        flops, nbytes = cost(config, cell)
        want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / ms
        assert reader(name).compute(traced) == pytest.approx(want)
        assert 0 < want < 100
    # a program whose rows carry no name scope, and none of the scan's
    # kernels (the parent's), reads nothing

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture() if not (r["kernel"] or "").startswith(
                    "selective_scan")]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    for name in ("device_ms_per_step.state_space",
                 "device_ms_per_step.gated_memory",
                 "device_ms_per_step.differential_attention",
                 "device_ms_per_step.cross_attention",
                 "selective_scan_roofline_share"):
        assert reader(name).compute(traced) is None, name


def test_the_counter_readers_read_the_programs_counters(monkeypatch):
    from paddle_tpu.observe.monitoring import runtime_stats

    chunks = reader("selective_scan_chunks_per_step")
    on_xla = reader("selective_scans_xla_per_step")
    for field in ("selective_scans_kernel", "selective_scans_xla",
                  "selective_scan_chunks"):
        monkeypatch.setattr(runtime_stats, field, 0)
    assert chunks.compute({}) is None           # no kernel call traced
    assert on_xla.compute({}) == 0              # and none on XLA: a number
    for _ in range(6):
        runtime_stats.record_selective_scan(True, 32)
    runtime_stats.record_selective_scan(False, 0)
    assert chunks.compute({}) == 192 and on_xla.compute({}) == 1
    # a program from before the counters: nothing to read, no raise
    monkeypatch.setattr(type(runtime_stats), "snapshot", lambda self: {})
    assert chunks.compute({}) is None and on_xla.compute({}) is None


def test_toy_phi4flash_cell_runs_the_harness(capfd):
    from paddle_tpu.observe.monitoring import runtime_stats

    before = runtime_stats.snapshot()
    result = bench_run.run_cell("tiny-phi4flash-host", 2**31 + 11, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out;
    # the toy's 32 positions are no whole chunk: its scans ran on XLA
    assert set(result["metrics"]) >= {"dispatch_ms.train",
                                      "compiles_in_window"}
    out = capfd.readouterr().out
    assert '"loss_fell": true' in out
    took = runtime_stats.delta(before)
    assert (took["shared_memory_reads"], took["shared_kv_reads"],
            took["differential_attention_calls"]) == (1, 1, 3)
    assert took["selective_scans_xla"] > 0
    assert took["selective_scans_kernel"] == 0
    assert took["short_conv_bias_calls"] > 0
    assert took["flash_attention_backward_fused"] == 3


def test_parity_script_compares_logits_and_every_leaf():
    import jax.numpy as jnp

    parity = load("phi4flash_parity")
    _, config, family = bench_run.load_cell("tiny-phi4flash-host",
                                            (BENCH, FIXTURES))
    config["training"]["initializer_range"] = 0.02
    parity.LAST, parity.Q_BLOCK, parity.TIME_BLOCK = 16, 8, 8
    r = parity.check_seed(config, family, 2**31 + 3)
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_loss"] and checks["f32_grads"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-3, r["f32"]
    names = set(r["f32"]["grad_err"])
    assert {"layer0.a_log", "layer2.w_x", "layer2.w_u", "layer3.wk",
            "layer3.lq1", "layer5.wq", "layer4.w1"} <= names
    # a key bias's gradient is 0 but for rounding: held against bq's
    got = parity.grad_errors(
        [np.ones(3), np.full(3, 1e-9)], [np.ones(3), np.zeros(3)],
        ["layer1.bq", "layer1.bk"])
    assert got["grad_err"]["layer1.bk"] == pytest.approx(1e-9)
    assert jnp.bfloat16 is not None
