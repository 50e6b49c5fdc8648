"""The `qwen3-next-80b-a3b` configuration, its family, the
`qwen3next-16k` cell and the readers PR 44 adds, on the CPU: the files
and BENCHMARK.json agree (entries looked up BY NAME: the next cell is
appended after this one), the configuration holds the catalog's numbers
and exactly its three cuts, the family's map onto the builder,
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
import kernel_counts_qwen3next as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
CELL, CONFIG = "qwen3next-16k", "qwen3-next-80b-a3b"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/"
          "blob/main/config.json")
CATALOG = {      # the catalog row's `config`, Qwen3-Next-80B-A3B-Instruct
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_READERS = {"device_ms_per_step.linear_attention": "device_trace",
               "device_ms_per_step.gated_attention": "device_trace",
               "gated_delta_roofline_share": "device_trace",
               "flash_d256_roofline_share": "device_trace",
               "gated_delta_chunks_per_step": "program_counter"}
T, D, V, LAYERS, HELD, RANKS = 16384, 2048, 18992, 4, 16, 32
HK, HV, DK, DV, H, HKV, HD, F = 16, 32, 128, 128, 16, 2, 256, 512


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
    assert V == 151936 // 8                         # the floor of an eighth
    assert config["published"]["num_experts"] == 512
    assert config["published"]["vocab_size"] == 151936
    assert config["published"]["num_hidden_layers"] == 48
    assert (config["expert_parallel_size"], config["expert_parallel_rank"],
            config["sequence_length"]) == (RANKS, 0, T)
    assert HELD * RANKS == 512
    # the rule that chose 32 chips a layer over 16, with both plans
    assert "15.64 GB" in config["reduced_why"]
    assert "12.78 GB" in config["reduced_why"]
    assert "32 chips share each layer" in config["deployment"]
    # no width, no head count, no rotary or linear key is cut
    for key in CATALOG:
        if key not in REDUCED:
            assert config[key] == CATALOG[key], key
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["recompute"], t["use_amp"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, "layer", True)
    # every matrix and the table from the builder's default N(0, 0.02)
    assert "initializer_range" not in t and "embedding_init_range" not in t
    assert {"layer_types", "norms", "qk_norm", "rope", "attention gate",
            "linear attention", "column order", "scan", "router",
            "shared expert", "prediction module", "packed documents",
            "unread keys", "auxiliary loss", "router update", "weights",
            "training", "sequence_length", "recomputation"} \
        <= set(config["assumed"])
    assert "precision" in config


def test_the_family_maps_the_published_keys_onto_the_builder():
    _, config, family = real()
    args = family.architecture(config)
    assert args["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert (args["qk_norm"], args["router"], args["zero_centered_norm"],
            args["attention_gate"], args["shared_expert_gate"]) == (
        "head", "softmax", True, "sigmoid", "sigmoid")
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_experts_per_tok",
                "norm_topk_prob", "rms_norm_eps", "rope_theta",
                "partial_rotary_factor", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "tie_word_embeddings"):
        assert args[key] == CATALOG[key], key
    assert (args["num_experts"], args["expert_parallel_size"],
            args["expert_parallel_rank"]) == (HELD, RANKS, 0)
    assert not {"model_type", "hidden_act", "use_sliding_window",
                "rope_scaling", "decoder_sparse_step", "mlp_only_layers",
                "full_attention_interval",
                "max_position_embeddings"} & set(args)
    import inspect

    from paddle_tpu.models import decoder

    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    assert set(config["training"]) <= (
        set(inspect.signature(decoder.build_model).parameters)
        | set(inspect.signature(decoder.decoder).parameters))
    for key, value in (("hidden_act", "gelu"), ("use_sliding_window", True),
                       ("rope_scaling", {"rope_type": "yarn"}),
                       ("decoder_sparse_step", 2), ("mlp_only_layers", [0])):
        with pytest.raises(NotImplementedError, match=key):
            family.architecture(dict(config, **{key: value}))
    # no model's name in the program
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    assert "qwen" not in f.read().lower(), name


def parameters(held):
    linear = (D * (2 * HK * DK + HV * DV) + D * HV * DV + D * 2 * HV
              + (2 * HK * DK + HV * DV) * 4 + HV * DV * D
              + 2 * HV + DV + D)
    full = 2 * D * H * HD + 2 * D * HKV * HD + H * HD * D + 2 * HD + D
    sparse = D + D * 512 + held * 3 * D * F + 3 * D * F + D
    return 3 * linear + full + LAYERS * sparse + 2 * V * D + D


def test_parameters_by_hand():
    """A linear layer's mixer 33.72 M, a full layer's 27.26 M, 3.146 M
    an expert; 424.3 M parameters at 16 held = 5.09 GB of float32 master
    weights and two Adam moments, and the 625.7 M = 7.51 GB at 32 held
    whose plan passed 15.0 GB."""
    assert round((D * 12288 + D * 64 + 8192 * 4 + 4096 * D) / 1e6, 2) \
        == 33.72
    assert round((2 * D * 4096 + 2 * D * 512 + 4096 * D) / 1e6, 2) == 27.26
    assert 3 * D * F == 3145728
    assert parameters(16) == 424340544
    assert parameters(32) == 625667136
    assert round(12 * parameters(16) / 1e9, 2) == 5.09
    assert round(12 * parameters(32) / 1e9, 2) == 7.51
    assert round(16 * parameters(16) / 1e9, 2) == 6.79


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (CONFIG, "b1-len16384-host", 1, None, 1, T,
                              "host", 4)
    assert len(cell["why"]) <= 200 and "1/32" in cell["why"]
    # the traffic is mellum2-16k's own, key for key
    mellum = bench_run.load_json(os.path.join(BENCH, "workloads",
                                              "mellum2-16k.json"))
    assert {k: v for k, v in cell.items()
            if k not in ("config", "why", "name")} \
        == {k: v for k, v in mellum.items() if k not in ("config", "why")}
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert CELL in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == CELL] == [{
        "name": CELL, "config": CONFIG, "traffic": "b1-len16384-host",
        "chips": 1, "why": cell["why"]}]
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": T, "unit": "tokens/s"}}


def test_train_flops_count_the_recurrence_in_its_sequential_form():
    """ISSUE 44's table from the shapes, to the last digit; the held
    experts' line at the 16 held the plan's rule chose (15.73 M at the
    32 held the table was written for)."""
    cell, config, family = real()
    pairs = T * (T + 1) // 2
    want = {
        "linear_projections": 3 * 2 * D * (12288 + 64 + 4096),
        "recurrence": 3 * HV * 6 * DK * DV,
        "full_projections": 2 * D * (2 * 4096 + 2 * 512 + 4096),
        "full_attention": 2 * 2 * 4096 * pairs / T,
        "router": LAYERS * 2 * D * 512,
        "shared_expert": LAYERS * 2 * D * (3 * F + 1),
        "experts": LAYERS * 10 / RANKS * 3 * 2 * D * F,
        "head": 2 * D * V}
    got = family.forward_flops_per_token(config, T)
    assert got == pytest.approx(want)
    m = {k: round(v / 1e6, 1) for k, v in got.items()}
    assert round((got["linear_projections"] + got["recurrence"]) / 1e6, 1) \
        == 211.6
    assert round(got["linear_projections"] / 3e6, 1) == 67.4
    assert round(got["recurrence"] / 3e6, 2) == 3.15
    assert (m["full_projections"], m["full_attention"]) == (54.5, 134.2)
    assert m["head"] == 77.8
    assert round((got["router"] + got["shared_expert"]) / 1e6, 1) == 33.6
    assert m["experts"] == 7.9
    assert round(2 * got["experts"] / 1e6, 1) == 15.7    # at 32 held
    total = sum(got.values())
    assert family.train_flops(config, cell) == pytest.approx(3 * total * T)
    assert family.train_flops(config, cell) == pytest.approx(25.54e12,
                                                             rel=1e-3)
    # the sequential count does not know the chunk
    assert "CHUNK" not in open(os.path.join(
        BENCH, "models", "qwen3_next.py")).read()


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    assert counts.layer_types(config) == ["linear_attention"] * 3 + [
        "full_attention"]
    assert counts.chunks_per_call(config, cell) == 32 * 256 == 8192
    flops, nbytes = counts.gated_delta_cost(config, cell)
    chunk = 9 * 2 * 64 * 128 * 128 + 3 * 2 * 64 * 64 * 128
    assert flops == 3 * 8192 * chunk
    rows = 8192 * 64
    assert nbytes == 3 * 2 * (rows * (15 * 128 + 3 * 64)
                              + 2 * 8192 * 128 * 128)
    # the bytes bound it on a v5e: 10.8 ms against 2.7 ms of products
    assert nbytes / 819e9 > 3 * flops / 197e12
    flops, nbytes = counts.flash_d256_cost(config, cell)
    assert flops == 14 * H * (T * (T + 1) // 2) * HD
    assert nbytes == 6 * T * (H * HD + HKV * HD) * 2
    assert not any(s.startswith(counts.FLASH_KERNELS)
                   for s in counts.SCAN_KERNELS)
    # the registered costs of the kernels are the same products
    from paddle_tpu.ops.pallas import KERNEL_COSTS, gated_delta  # noqa: F401

    shapes = [((32, T, 128), 2)] * 4
    fwd, _ = KERNEL_COSTS["gated_delta_fwd"](shapes, None)
    bwd, _ = KERNEL_COSTS["gated_delta_bwd"](shapes, None)
    assert 3 * (fwd + bwd) == counts.gated_delta_cost(config, cell)[0]


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
        row("fusion.1", "matmul", 0.050, "linear_attention", "mul", None,
            3e9),
        row("fusion.2", "elementwise", 0.030, "checkpoint/linear_attention",
            "gated_delta_rule"),
        row("custom-call.1", "custom_call", 0.020, "linear_attention",
            "gated_delta_rule", "gated_delta_fwd"),
        row("custom-call.2", "custom_call", 0.030, "linear_attention",
            "gated_delta_rule", "gated_delta_bwd"),
        row("fusion.3", "matmul", 0.040, "gated_attention", "mul", None,
            2e9),
        row("custom-call.3", "custom_call", 0.100, "gated_attention",
            "flash_attention", "flash_fwd"),
        row("custom-call.4", "custom_call", 0.160, "gated_attention",
            "flash_attention", "flash_dkv"),
        row("custom-call.5", "custom_call", 0.140, "gated_attention",
            "flash_attention", "flash_dq"),
        row("fusion.4", "matmul", 0.070, "", "moe_dropless", None, 1e9),
        row("fusion.5", "elementwise", 0.005, "", "adam"),
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
    assert reader("device_ms_per_step.linear_attention").compute(
        traced) == pytest.approx((50 + 30 + 20 + 30) / 2)
    assert reader("device_ms_per_step.gated_attention").compute(
        traced) == pytest.approx((40 + 100 + 160 + 140) / 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    cell, config = traced["cell"], traced["config"]
    for name, cost, ms in (
            ("gated_delta_roofline_share", counts.gated_delta_cost, 25.0),
            ("flash_d256_roofline_share", counts.flash_d256_cost, 200.0)):
        flops, nbytes = cost(config, cell)
        want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / ms
        assert reader(name).compute(traced) == pytest.approx(want)
        assert 0 < want < 100
    # a program whose rows carry no name scope and no scan kernel (the
    # parent's) reads nothing and does not raise

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture() if not (r["kernel"] or "").startswith(
                    "gated_delta")]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    assert reader("device_ms_per_step.linear_attention").compute(
        traced) is None
    assert reader("device_ms_per_step.gated_attention").compute(
        traced) is None
    assert reader("gated_delta_roofline_share").compute(traced) is None


def test_the_chunk_count_reads_the_programs_counter(monkeypatch):
    from paddle_tpu.observe.monitoring import runtime_stats

    chunks = reader("gated_delta_chunks_per_step")
    monkeypatch.setattr(runtime_stats, "gated_delta_calls", 0)
    monkeypatch.setattr(runtime_stats, "gated_delta_chunks", 0)
    assert chunks.compute({}) is None        # the XLA lowering: no call
    for _ in range(9):
        runtime_stats.record_gated_delta(8192)
    assert chunks.compute({}) == 9 * 8192 == 73728
    assert counts.scan_chunks() == (9, 73728)
    # a program from before the counters: nothing to read, no raise
    monkeypatch.setattr(type(runtime_stats), "snapshot", lambda self: {})
    assert chunks.compute({}) is None


def test_toy_cell_runs_the_harness(capfd):
    # 5 s: `loss_fell` wants a pool's worth of steps (4) in the window,
    # and a step of this toy took a second on a host loaded by six
    # workers (0.15 s alone)
    result = bench_run.run_cell("tiny-qwen3next-host", 2**31 + 11, 5.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out;
    # heads of 16 run the scan's XLA lowering: no chunk count either
    assert set(result["metrics"]) >= {"dispatch_ms.train",
                                      "compiles_in_window"}
    assert '"loss_fell": true' in capfd.readouterr().out


def test_parity_script_compares_logits_routing_and_every_leaf(monkeypatch):
    parity = load("qwen3next_parity")
    _, config, family = bench_run.load_cell("tiny-qwen3next-host",
                                            (BENCH, FIXTURES))
    monkeypatch.setattr(parity.base, "LAST", 16)
    monkeypatch.setattr(parity.base, "Q_BLOCK", 16)
    monkeypatch.setattr(parity.base, "GRAD_Q_BLOCK", 16)
    r = parity.check_seed(config, family, 2**31 + 3)
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_loss"]
    assert checks["f32_routing"] and checks["f32_held_counts"]
    assert checks["grads_are_compared"] and checks["f32_grads"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-4
    assert len(r["f32"]["grad_err"]) == 3 * 18 + 17 + 3
