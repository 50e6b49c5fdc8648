"""The `sdar-30b-a3b` configuration, its family, the `sdar-8k` cell and
the readers PR 47 adds, on the CPU: the files and BENCHMARK.json agree
(entries looked up BY NAME: the next cell is appended after this one),
the configuration holds the catalog's numbers and exactly its three
cuts, the family's map onto the builder, `train_flops` and the kernel
counts against hand counts, `units` counts the document and not the
doubled rows, each reader on a fixture and without a trace, the parity
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
import kernel_counts_sdar as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
SOURCE = ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
          "config.json")
CATALOG = {      # the catalog row's `config`, SDAR-30B-A3B-Chat
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_READERS = {
    "device_ms_per_step.block_diffusion_attention": "device_trace",
    "flash_block_diffusion_roofline_share": "device_trace",
    "flash_block_diffusion_block_visit_ratio": "program_counter",
    "device_ms_per_step.placed_experts": "device_trace",
    "placed_expert_row_share": "program_counter",
    "placed_expert_matmul_roofline_share": "device_trace"}
L, B, D, H, HKV, HD, F, V, LAYERS = 8192, 4, 2048, 32, 4, 128, 768, 18992, 6
PAIRS = 67141632


def real():
    return bench_run.load_cell("sdar-8k", (BENCH,))


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
            config["vocab_size"]) == (LAYERS, 16, V)
    assert V == 151936 // 8
    assert config["published"]["num_experts"] == 128
    assert config["published"]["vocab_size"] == 151936
    assert config["published"]["num_hidden_layers"] == 48
    assert (config["expert_parallel_size"], config["expert_parallel_rank"],
            config["sequence_length"]) == (8, 0, L)
    assert (config["block_length"], config["noise_t_min"],
            config["mask_token_id"]) == (B, 1e-3, V - 1)
    # no width and no head count is cut
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "intermediate_size", "num_experts_per_tok", "rope_theta"):
        assert config[key] == CATALOG[key], key
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == "sdar-30b-a3b"][0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmarks/configs/sdar-30b-a3b.json"
    assert len(entry["why"]) <= 200
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["recompute"], t["use_amp"]) == (
        2e-5, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, "layer", True)
    assert {"block_length", "noise schedule", "objective", "mask id",
            "qk_norm", "router", "rope", "unread keys", "router update",
            "weights", "training", "sequence_length",
            "recomputation"} <= set(config["assumed"])
    assert "8 chips share each layer" in config["deployment"]
    # mellum2's init: a unit-variance table under small matrices
    assert (t["initializer_range"], t["embedding_init_range"]) == (0.002, 1.0)


def test_the_family_maps_the_published_keys_onto_the_builder():
    _, config, family = real()
    args = family.architecture(config)
    assert (args["qk_norm"], args["router"], args["objective"],
            args["block_length"]) == ("head", "softmax", "block_diffusion", B)
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
                "rope_theta", "tie_word_embeddings"):
        assert args[key] == CATALOG[key], key
    assert (args["num_experts"], args["expert_parallel_size"],
            args["expert_parallel_rank"]) == (16, 8, 0)
    assert not {"model_type", "max_window_layers", "hidden_act",
                "use_sliding_window", "sliding_window", "mlp_only_layers",
                "decoder_sparse_step", "max_position_embeddings",
                "mask_token_id", "noise_t_min"} & set(args)
    import inspect

    from paddle_tpu.models import decoder

    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    assert set(config["training"]) <= (
        set(inspect.signature(decoder.build_model).parameters)
        | set(inspect.signature(decoder.decoder).parameters))
    for key, value in (("hidden_act", "gelu"), ("attention_bias", True),
                       ("use_sliding_window", True),
                       ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("rope_scaling", {"type": "linear"})):
        with pytest.raises(NotImplementedError, match=key):
            family.architecture(dict(config, **{key: value}))
    # no model's name in the program
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    assert "sdar" not in f.read().lower(), name


def test_parameters_by_hand():
    """645.6 M parameters: 7.75 GB of float32 master weights and two
    Adam moments, 10.33 GB with a float32 gradient beside them; at four
    layers 456.3 M; the published depth and experts: the "30B-A3B"."""
    attention = 2 * D * H * HD + 2 * D * HKV * HD
    assert attention == 18874368                         # "18.87 M"
    expert = 3 * D * F
    assert expert == 4718592                             # "4.72 M"
    layer = attention + D * 128 + 16 * expert + 2 * D + 2 * HD
    total = 2 * V * D + LAYERS * layer + D
    assert total == 645623296
    assert round(12 * total / 1e9, 2) == 7.75
    assert round(16 * total / 1e9, 2) == 10.33
    assert 2 * V * D + 4 * layer + D == 456346624
    whole = 48 * (attention + D * 128 + 128 * expert) + 2 * 151936 * D
    assert round(whole / 1e9, 1) == 30.5
    active = 48 * (attention + D * 128 + 8 * expert) + 2 * 151936 * D
    assert round(active / 1e9, 1) == 3.4


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (
        "sdar-30b-a3b", "b1-len8192-host", 1, None, 1, L, "host", 4)
    assert len(cell["why"]) <= 200 and "16/128" in cell["why"]
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert "sdar-8k" in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == "sdar-8k"] == [{
        "name": "sdar-8k", "config": "sdar-30b-a3b",
        "traffic": "b1-len8192-host", "chips": 1, "why": cell["why"]}]
    assert len(bj["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    # the document's tokens: not the 2 L rows, not the half a draw masks
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": L, "unit": "tokens/s"}}


def test_train_flops_count_the_pairs_the_mask_allows():
    cell, config, family = real()
    q, kv = H * HD, HKV * HD
    n = L // B
    assert counts.allowed_pairs(L, B) == PAIRS
    assert (L * B, B * B * n * (n - 1) // 2, B * B * n * (n + 1) // 2) \
        == (32768, 33538048, 33570816)
    want = {"projections": LAYERS * 2 * L * 2 * (2 * D * q + 2 * D * kv),
            "block_diffusion_attention": LAYERS * 2 * 2 * q * PAIRS,
            "router": LAYERS * 2 * L * 2 * D * 128,
            "experts": LAYERS * 2 * L * 1 * 3 * 2 * D * F,
            "head": L * 2 * D * V}
    got = family.forward_flops(config, L)
    assert got == pytest.approx(want)
    tera = {k: round(v / 1e12, 2) for k, v in got.items()}
    assert tera == {"projections": 3.71, "block_diffusion_attention": 6.60,
                    "router": 0.05, "experts": 0.93, "head": 0.64}
    total = sum(got.values())
    assert total == pytest.approx(11.93e12, rel=1e-3)
    assert family.train_flops(config, cell) == pytest.approx(3 * total) \
        == pytest.approx(35.78e12, rel=1e-3)
    assert round(100 * got["block_diffusion_attention"] / total) == 55
    # counted as the causal half of 2 L the mask would put mfu 1.55 x up
    causal = 2 * L * (2 * L + 1) // 2
    as_causal = total + LAYERS * 4 * q * (causal - PAIRS)
    assert round(as_causal / total, 2) == 1.55


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    # one block: itself twice (clean and noised); two: + the prefix
    assert counts.allowed_pairs(4, 4) == 16 + 16
    assert counts.allowed_pairs(8, 4) == 32 + 16 * 1 + 16 * 3
    flops, nbytes = counts.flash_block_diffusion_cost(config, cell)
    assert flops == LAYERS * 14 * H * PAIRS * HD
    assert nbytes == LAYERS * 6 * 2 * L * (H * HD + HKV * HD) * 2
    # no other cell's list of names catches these kernels by prefix
    import kernel_counts_mellum as mellum

    for name in counts.KERNELS:
        assert not name.startswith(mellum.GROUPED_KERNELS)
        assert not name.startswith(mellum.WINDOW_KERNELS)
        assert not name.startswith(kernel_counts.FLASH_KERNELS)


def test_make_batch_noises_a_document_of_the_vocabulary_slice():
    cell, config, family = real()
    a = family.make_batch(config, cell, np.random.default_rng(2**31 + 5))
    b = family.make_batch(config, cell, np.random.default_rng(2**31 + 5))
    assert sorted(a) == ["labels", "loss_weights", "tokens"]
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    assert a["tokens"].shape == (1, 2 * L) and a["labels"].shape == (1, L)
    assert a["loss_weights"].shape == (1, L)
    assert 1 <= a["labels"].min() and a["labels"].max() <= V - 2
    noised = a["tokens"][:, L:]
    masked = noised == V - 1
    np.testing.assert_array_equal(a["tokens"][:, :L], a["labels"])
    np.testing.assert_array_equal(noised[~masked], a["labels"][~masked])
    np.testing.assert_array_equal(a["loss_weights"] > 0, masked)
    assert 0.45 < masked.mean() < 0.55
    assert a["loss_weights"].max() <= 1000.0 * (1 + 1e-6)
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
        assert module.META["cells"] == ["sdar-8k"] == listed[name][
            "workloads"]
        assert module.META["moves"] == "mfu" == listed[name]["moves"]
        assert module.META["unit"] == listed[name]["unit"]
        assert module.META["layer"] == listed[name]["layer"]
        assert module.META["source"] == source == listed[name]["source"]
        if source == "device_trace":
            assert module.compute(no_trace) is None
    readers = bench_run.layer_readers("sdar-8k", (BENCH,))
    everywhere = {m["name"] for m in benchmark_json()["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert "sdar-8k" in readers[name].META["cells"]
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
        row("fusion.1", "matmul", 0.050, "block_diffusion_attention", "mul",
            None, 3e9),
        row("fusion.2", "elementwise", 0.010,
            "checkpoint/block_diffusion_attention", "rope"),
        row("custom-call.1", "custom_call", 0.100,
            "block_diffusion_attention", "flash_attention",
            "flash_block_diffusion_fwd"),
        row("custom-call.2", "custom_call", 0.200,
            "block_diffusion_attention", "flash_attention",
            "flash_block_diffusion_dkv"),
        row("fusion.4", "matmul", 0.070, "", "moe_dropless", None, 1e9),
        row("custom-call.3", "custom_call", 0.030, "", None, "ragged_dot"),
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
    assert reader("device_ms_per_step.block_diffusion_attention").compute(
        traced) == pytest.approx((50 + 10 + 100 + 200) / 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    flops, nbytes = counts.flash_block_diffusion_cost(traced["config"],
                                                      traced["cell"])
    want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / 150.0
    assert reader("flash_block_diffusion_roofline_share").compute(
        traced) == pytest.approx(want)
    assert flops / 197e12 > nbytes / 819e9          # compute bounds it
    assert 0 < want < 100
    # a program whose rows carry no name scope, or that ran no such
    # kernel (the parent's), reads nothing and does not raise

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture() if not r["kernel"]]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    assert reader("device_ms_per_step.block_diffusion_attention").compute(
        traced) is None
    assert reader("flash_block_diffusion_roofline_share").compute(
        traced) is None


def test_the_placed_experts_readers_on_a_fixture(traced, monkeypatch):
    """The expert op's rows and its grouped-matmul kernels; the share
    from the op's counters; the roofline over the rows really held, all
    2 L rows of all six layers routed."""
    import kernel_counts_lfm2

    assert reader("device_ms_per_step.placed_experts").compute(
        traced) == pytest.approx((70 + 30) / 2)
    share = reader("placed_expert_row_share")
    matmuls = reader("placed_expert_matmul_roofline_share")
    monkeypatch.setattr(kernel_counts_lfm2, "held_row_share", lambda: None)
    assert share.compute(traced) is None and matmuls.compute(traced) is None
    monkeypatch.setattr(kernel_counts_lfm2, "held_row_share", lambda: 0.125)
    assert share.compute(traced) == 12.5
    rows = counts.placed_rows_per_layer_step(traced["config"],
                                             traced["cell"])
    assert rows == 2 * L * 8 / 8
    flops, nbytes = counts.placed_expert_matmul_cost(
        traced["config"], traced["cell"], rows)
    assert flops == LAYERS * 9 * 2 * rows * D * F
    assert nbytes == LAYERS * 9 * 2 * (rows * D + rows * F + 16 * D * F)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / 15.0
    assert matmuls.compute(traced) == pytest.approx(want)
    assert 0 < want < 100


def test_the_visit_ratio_reads_the_programs_counters(monkeypatch):
    from paddle_tpu.observe.monitoring import runtime_stats

    ratio = reader("flash_block_diffusion_block_visit_ratio")
    for field in ("calls", "blocks_visited", "blocks_allowed"):
        monkeypatch.setattr(runtime_stats, "flash_block_diffusion_" + field,
                            0)
    assert ratio.compute({}) is None         # no such call traced
    runtime_stats.record_flash_block_diffusion(288, 288)
    runtime_stats.record_flash_block_diffusion(288, 288)
    assert ratio.compute({}) == 1.0
    assert counts.visited_blocks() == (576, 576, 2)
    runtime_stats.record_flash_block_diffusion(528, 288)
    assert ratio.compute({}) == pytest.approx((576 + 528) / (3 * 288))
    # a program from before the counters: nothing to read, no raise
    monkeypatch.setattr(type(runtime_stats), "snapshot", lambda self: {})
    assert ratio.compute({}) is None


def test_toy_sdar_cell_runs_the_harness(capfd):
    from paddle_tpu.observe.monitoring import runtime_stats

    before = runtime_stats.snapshot()
    result = bench_run.run_cell("tiny-sdar-host", 2**31 + 11, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out
    assert set(result["metrics"]) >= {"dispatch_ms.train",
                                      "compiles_in_window"}
    out = capfd.readouterr().out
    assert '"loss_fell": true' in out
    took = runtime_stats.delta(before)
    assert (took["flash_attention_backward_fused"],
            took["flash_attention_backward_split"]) == (2, 0)
    assert took["flash_block_diffusion_calls"] > 0
    assert took["flash_block_diffusion_blocks_visited"] \
        == took["flash_block_diffusion_blocks_allowed"] > 0


def _toy_weights(seed, monkeypatch=None):
    """{parameter: array} after the toy cell's start-up run, and the
    names of its table and routers; with `monkeypatch` the placement
    is left out."""
    cell, config, family = bench_run.load_cell("tiny-sdar-host",
                                               (BENCH, FIXTURES))
    if monkeypatch is not None:
        monkeypatch.setattr(family, "place_experts", lambda config: None)
    _, main, scope, _ = bench_run.build(config, cell, family, seed)
    ops = main.global_block().ops
    table = next(op for op in ops
                 if op.type == "lookup_table").input("W")[0]
    routers = [op.input("GateW")[0] for op in ops
               if op.type == "moe_dropless"]
    weights = {p.name: np.asarray(scope.find_var(p.name))
               for p in main.global_block().all_parameters()}
    return config, weights, table, routers


@pytest.mark.parametrize("seed", [3, 77, 2**31 + 5])
def test_the_placement_gives_each_rank_one_of_the_mask_ids_experts(
        seed, monkeypatch):
    """After the start-up run every router sends the mask id's row to
    one expert of each rank's block, the best to rank 0, and each
    block goes on with experts the row does not take; the router is a
    permutation of the columns the seed drew, the other experts in
    their order, and no other parameter moved."""
    config, placed, table, routers = _toy_weights(seed)
    _, drawn, _, _ = _toy_weights(seed, monkeypatch)
    held, k = config["num_experts"], config["num_experts_per_tok"]
    assert len(routers) == config["num_hidden_layers"]
    mask_row = placed[table][config["mask_token_id"]]
    for name in routers:
        best = np.argsort(-(mask_row @ placed[name]))[:k]
        assert best.tolist() == [rank * held for rank in range(k)]
        was = np.argsort(-(mask_row @ drawn[name]))
        others = np.sort(was[k:])
        columns = np.concatenate([was[:k], others])[
            _family().placement_order(config)]
        np.testing.assert_array_equal(placed[name], drawn[name][:, columns])
    for name in set(placed) - set(routers):
        np.testing.assert_array_equal(placed[name], drawn[name])


def _family():
    return bench_run.load_cell("sdar-8k", (BENCH,))[2]


def test_placement_order_by_hand():
    """At the cell's sizes: rank r's 16 columns are the mask id's r-th
    expert (entries 0-7 of [chosen ; others]) and 15 of the 120 others
    (entries 8-127) in their order; a permutation of all 128; a
    deployment with other than one chosen expert a rank is not built."""
    _, config, family = real()
    order = family.placement_order(config)
    assert order[:18].tolist() == [0] + list(range(8, 23)) + [1, 23]
    assert order[-16:].tolist() == [7] + list(range(113, 128))
    assert sorted(order.tolist()) == list(range(128))
    with pytest.raises(NotImplementedError, match="one a rank"):
        family.placement_order(dict(config, num_experts_per_tok=4))


def test_parity_script_runs_at_a_toy_size_inside_its_limits():
    """`sdar_parity.py` end to end on the CPU: float32 inside its
    limits, every leaf compared, the held rows reported."""
    parity = load("sdar_parity")
    _, config, family = bench_run.load_cell("tiny-sdar-host",
                                            (BENCH, FIXTURES))
    base = parity.base
    was = base.LAST, parity.Q_BLOCK, parity.GRAD_Q_BLOCK
    base.LAST, parity.Q_BLOCK, parity.GRAD_Q_BLOCK = 16, 8, 8
    try:
        r = parity.check_seed(config, family, 2**31 + 3)
    finally:
        base.LAST, parity.Q_BLOCK, parity.GRAD_Q_BLOCK = was
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_loss"]
    assert checks["f32_routing"] and checks["f32_held_counts"]
    assert checks["grads_are_compared"] and checks["f32_grads"]
    assert checks["share_is_a_share"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-4
    assert len(r["rows"]["held_row_share_by_layer"]) == 2
    assert 0.3 < r["rows"]["masked_share"] < 0.7
