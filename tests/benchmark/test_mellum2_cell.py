"""The `mellum2-12b-a2.5b` configuration, its family, the `mellum2-16k`
cell and the readers PR 38 adds, on the CPU: the files and
BENCHMARK.json agree (entries looked up BY NAME: the next cell is
appended after this one), the configuration holds the catalog's numbers
and exactly its five cuts, the family's map onto the builder,
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
import kernel_counts_mellum as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CATALOG = {      # the catalog row's `config`, Mellum2-12B-A2.5B-Instruct
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_experts", "vocab_size"]
NEW_READERS = {"device_ms_per_step.sliding_attention": "device_trace",
               "device_ms_per_step.full_attention": "device_trace",
               "flash_window_roofline_share": "device_trace",
               "flash_grouped_roofline_share": "device_trace",
               "flash_window_block_visit_ratio": "program_counter"}
T, D, H, HKV, HD, W, F, V, LAYERS = 16384, 2304, 32, 4, 128, 1024, 896, \
    12288, 8


def real():
    return bench_run.load_cell("mellum2-16k", (BENCH,))


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
    assert config["num_hidden_layers"] == LAYERS        # two whole periods
    assert config["layer_types"] == PERIOD * 2
    assert config["mlp_layer_types"] == ["sparse"] * LAYERS
    assert (config["num_experts"], config["vocab_size"]) == (8, V)
    assert config["published"]["num_experts"] == 64
    assert config["published"]["vocab_size"] == 98304
    assert config["published"]["num_hidden_layers"] == 28
    assert (config["expert_parallel_size"], config["expert_parallel_rank"],
            config["sequence_length"]) == (8, 0, T)
    # no width, no head count, no window, no RoPE key is cut
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "intermediate_size", "num_experts_per_tok", "sliding_window",
                "rope_parameters"):
        assert config[key] == CATALOG[key], key
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == "mellum2-12b-a2.5b"][0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/mellum2-12b-a2.5b.json"
    assert len(entry["why"]) <= 200
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["recompute"], t["use_amp"]) == (
        2e-5, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, "layer", True)
    # a continued pre-training's peak rate, as sdar-30b-a3b states it: at
    # OLMoE's 4e-4 the routing drifts inside a run and the tail follows
    # the seed (PERF.md section 2, PR 64)
    assert {"qk_norm", "router", "window edge", "rope", "prediction module",
            "unread keys", "router update", "weights", "training",
            "sequence_length", "recomputation"} <= set(config["assumed"])
    assert "8 chips share each layer" in config["deployment"]
    # the recipe's init: a unit-variance table under small matrices, so
    # that an untrained router does not collapse (PERF.md, PR 38)
    assert (t["initializer_range"], t["embedding_init_range"]) == (0.002, 1.0)
    assert "ONE direction" in config["assumed"]["weights"]


def test_the_family_maps_the_published_keys_onto_the_builder():
    _, config, family = real()
    args = family.architecture(config)
    assert (args["qk_norm"], args["router"]) == ("head", "softmax")
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
                "sliding_window", "rope_parameters", "tie_word_embeddings"):
        assert args[key] == CATALOG[key], key
    assert (args["num_experts"], args["expert_parallel_size"],
            args["expert_parallel_rank"]) == (8, 8, 0)
    assert not {"model_type", "max_window_layers", "hidden_act",
                "mlp_layer_types", "use_sliding_window",
                "max_position_embeddings"} & set(args)
    import inspect

    from paddle_tpu.models import decoder

    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    assert set(config["training"]) <= (
        set(inspect.signature(decoder.build_model).parameters)
        | set(inspect.signature(decoder.decoder).parameters))
    for key, value in (("hidden_act", "gelu"), ("attention_bias", True),
                       ("use_sliding_window", False),
                       ("mlp_layer_types", ["dense"] * LAYERS)):
        with pytest.raises(NotImplementedError, match=key):
            family.architecture(dict(config, **{key: value}))
    # no model's name in the program
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    assert "mellum" not in f.read().lower(), name


def test_parameters_by_hand():
    """624.1 M parameters: 7.49 GB of float32 master weights and two
    Adam moments, 9.98 GB with a float32 gradient beside them; the
    published depth and experts: the "12B-A2.5B"."""
    attention = 2 * D * H * HD + 2 * D * HKV * HD
    assert attention == 21233664                         # "21.23 M"
    expert = 3 * D * F
    assert expert == 6193152                             # "6.19 M"
    layer = attention + D * 64 + 8 * expert + 2 * D + 2 * HD
    total = 2 * V * D + LAYERS * layer + D
    assert total == 624075008
    assert round(12 * total / 1e9, 2) == 7.49
    assert round(16 * total / 1e9, 2) == 9.99
    whole = 28 * (attention + D * 64 + 64 * expert) + 2 * 98304 * D
    assert round(whole / 1e9, 2) == 12.15
    active = 28 * (attention + D * 64 + 8 * expert) + 2 * 98304 * D
    assert round(active / 1e9, 2) == 2.44


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (
        "mellum2-12b-a2.5b", "b1-len16384-host", 1, None, 1, T, "host", 4)
    assert len(cell["why"]) <= 200 and "1/8" in cell["why"]
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert "mellum2-16k" in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == "mellum2-16k"] == [{
        "name": "mellum2-16k", "config": "mellum2-12b-a2.5b",
        "traffic": "b1-len16384-host", "chips": 1, "why": cell["why"]}]
    assert len(bj["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": T, "unit": "tokens/s"}}


def test_mellum_train_flops_count_the_window_layers_by_the_band():
    cell, config, family = real()
    q, kv = H * HD, HKV * HD
    causal = T * (T + 1) // 2
    band = W * T - W * (W - 1) // 2
    assert (causal, band) == (134225920, 16253440)
    assert family.score_pairs(T) == causal
    assert family.score_pairs(T, W) == band
    want = {"projections": LAYERS * 2 * (2 * D * q + 2 * D * kv),
            "full_attention": 2 * 2 * 2 * q * causal / T,
            "sliding_attention": 6 * 2 * 2 * q * band / T,
            "router": LAYERS * 2 * D * 64,
            "experts": LAYERS * 1 * 3 * 2 * D * F,
            "head": 2 * D * V}
    got = family.forward_flops_per_token(config, T)
    assert got == pytest.approx(want)
    total = sum(got.values())
    assert total == pytest.approx(863.8e6, rel=1e-4)
    assert family.train_flops(config, cell) == pytest.approx(
        3 * total * T) == pytest.approx(42.46e12, rel=1e-3)
    share = {k: 100 * v / total for k, v in got.items()}
    assert round(share["projections"], 1) == 39.3
    assert round(share["full_attention"], 1) == 31.1
    assert round(share["sliding_attention"], 1) == 11.3
    assert round(share["experts"], 1) == 11.5
    assert round(share["head"], 1) == 6.6
    # counted as full layers the window layers would put mfu 1.6 x up
    as_full = total - got["sliding_attention"] + 3 * got["full_attention"]
    assert round(as_full / total, 1) == 1.8


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    assert counts.band_pairs(T, W) == 16253440
    assert counts.causal_pairs(T) == 134225920
    # a window of one key is the diagonal; one that holds every key the
    # causal half
    assert counts.band_pairs(10, 1) == 10
    assert counts.band_pairs(10, 10) == counts.band_pairs(10, 99) \
        == counts.causal_pairs(10)
    flops, nbytes = counts.flash_window_cost(config, cell)
    assert flops == 6 * 14 * H * 16253440 * HD
    assert nbytes == 6 * 6 * T * (H * HD + HKV * HD) * 2
    flops, nbytes = counts.flash_grouped_cost(config, cell)
    assert flops == 2 * 14 * H * 134225920 * HD
    assert nbytes == 2 * 6 * T * (H * HD + HKV * HD) * 2
    # neither list of names catches the other's kernels by prefix
    assert not any(w.startswith(counts.GROUPED_KERNELS)
                   for w in counts.WINDOW_KERNELS)
    assert not any(g.startswith(counts.WINDOW_KERNELS)
                   for g in counts.GROUPED_KERNELS)


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
        assert module.META["cells"] == ["mellum2-16k"] == listed[name][
            "workloads"]
        assert module.META["moves"] == "mfu" == listed[name]["moves"]
        assert module.META["unit"] == listed[name]["unit"]
        assert module.META["layer"] == listed[name]["layer"]
        assert module.META["source"] == source == listed[name]["source"]
        if source == "device_trace":
            assert module.compute(no_trace) is None
    readers = bench_run.layer_readers("mellum2-16k", (BENCH,))
    everywhere = {m["name"] for m in benchmark_json()["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert "mellum2-16k" in readers[name].META["cells"]
    assert not set(NEW_READERS) & set(
        bench_run.layer_readers("ouro-4k", (BENCH,)))


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
        row("fusion.2", "elementwise", 0.010, "checkpoint/sliding_attention",
            "rope"),
        row("custom-call.1", "custom_call", 0.040, "sliding_attention",
            "flash_attention", "flash_window_fwd"),
        row("custom-call.2", "custom_call", 0.060, "sliding_attention",
            "flash_attention", "flash_window_dkv"),
        row("fusion.3", "matmul", 0.040, "full_attention", "mul", None, 2e9),
        row("custom-call.3", "custom_call", 0.100, "full_attention",
            "flash_attention", "flash_fwd"),
        row("custom-call.4", "custom_call", 0.140, "full_attention",
            "flash_attention", "flash_dkv"),
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
    assert reader("device_ms_per_step.sliding_attention").compute(
        traced) == pytest.approx((50 + 10 + 40 + 60) / 2)
    assert reader("device_ms_per_step.full_attention").compute(
        traced) == pytest.approx((40 + 100 + 140) / 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    cell, config = traced["cell"], traced["config"]
    for name, cost, ms in (
            ("flash_window_roofline_share", counts.flash_window_cost, 50.0),
            ("flash_grouped_roofline_share", counts.flash_grouped_cost,
             120.0)):
        flops, nbytes = cost(config, cell)
        want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / ms
        assert reader(name).compute(traced) == pytest.approx(want)
        assert 0 < want < 100
    # a program whose rows carry no name scope (the parent's) reads
    # nothing; one without the window kernels no share

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture() if not (r["kernel"] or "").startswith(
                    "flash_window")]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    assert reader("device_ms_per_step.sliding_attention").compute(
        traced) is None
    assert reader("device_ms_per_step.full_attention").compute(
        traced) is None
    assert reader("flash_window_roofline_share").compute(traced) is None


def test_the_visit_ratio_reads_the_programs_two_counters(monkeypatch):
    from paddle_tpu.observe.monitoring import runtime_stats

    ratio = reader("flash_window_block_visit_ratio")
    monkeypatch.setattr(runtime_stats, "flash_window_blocks_visited", 0)
    monkeypatch.setattr(runtime_stats, "flash_window_blocks_allowed", 0)
    assert ratio.compute({}) is None              # no window call traced
    runtime_stats.record_flash_window_blocks(96, 93)
    runtime_stats.record_flash_window_blocks(96, 93)
    assert ratio.compute({}) == pytest.approx(96 / 93)
    # a program from before the counters: nothing to read, no raise
    monkeypatch.setattr(type(runtime_stats), "snapshot", lambda self: {})
    assert ratio.compute({}) is None


def test_toy_mellum_cell_runs_the_harness(capfd):
    from paddle_tpu.observe.monitoring import runtime_stats

    before = runtime_stats.snapshot()
    result = bench_run.run_cell("tiny-mellum-host", 2**31 + 11, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out
    assert set(result["metrics"]) >= {"dispatch_ms.train",
                                      "compiles_in_window"}
    out = capfd.readouterr().out
    assert '"loss_fell": true' in out
    took = runtime_stats.delta(before)
    assert (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"]) == (4, 0)
    assert took["flash_window_blocks_visited"] \
        >= took["flash_window_blocks_allowed"] > 0


def test_parity_script_compares_logits_routing_and_every_leaf():
    parity = load("mellum_parity")
    last = parity.LAST
    experts = np.tile(np.arange(2), (4, last, 1))
    want = {"logits": np.zeros((last, 5), np.float32), "loss": 2.0,
            "experts": experts, "counts": np.ones((4, 2), np.int64),
            "grad_names": ["embed", "layer0.wq", "layer0.router", "head"],
            "grads": [np.ones((3, 2), np.float32),
                      np.full((2, 2), 3.0, np.float32),
                      np.zeros((2, 2), np.float32),
                      np.full((4,), 2.0, np.float32)]}
    got = dict(want, logits=want["logits"].copy(), loss=2.002,
               experts=experts.copy(),
               grads=[np.ones((3, 2), np.float32),
                      np.full((2, 2), 3.0, np.float32),
                      np.zeros((2, 2), np.float32),
                      np.full((4,), 2.2, np.float32)])
    got["logits"][3, 1] = 0.02
    got["logits"][7, 0] = 5.0             # a token routed otherwise
    got["experts"][2, 7] = [0, 3]
    c = parity.compare(got, want)
    assert c["logit_err_max"] == pytest.approx(0.02)
    assert c["logit_err_all_max"] == pytest.approx(5.0)
    assert c["flipped_share"] == pytest.approx(1 / (4 * last))
    assert c["flipped_in_tail"] == 1
    assert c["loss_err"] == pytest.approx(0.002)
    assert c["grad_err_worst"] == pytest.approx(0.1, rel=1e-5)
    assert c["grad_err_worst_leaf"] == "head"
    assert c["grad_dead_leaves"] == ["layer0.router"]
    # and end to end at a toy size on the CPU: float32 inside its limits
    _, config, family = bench_run.load_cell("tiny-mellum-host",
                                            (BENCH, FIXTURES))
    parity.LAST, parity.Q_BLOCK, parity.GRAD_Q_BLOCK = 16, 8, 8
    r = parity.check_seed(config, family, 2**31 + 3)
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_loss"]
    assert checks["f32_routing"] and checks["f32_held_counts"]
    assert checks["grads_are_compared"] and checks["f32_grads"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-4
