"""The `lfm2-24b-a2b` configuration, its family, the `lfm2-8k` cell and
the readers PR 30 adds, on the CPU: the files and BENCHMARK.json agree,
the configuration holds the catalog's numbers and exactly the five
cuts, `train_flops` and the count functions against hand counts, each
reader on a fixture and without a trace, the two copies of the plain
reference, the parity script's arithmetic, and a toy cell through
`run_cell`.  No number from here is a speed.
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
import kernel_counts_lfm2 as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
PERIOD = ["full_attention", "conv", "conv", "conv"]
CATALOG = {      # the catalog row's `config`, LFM2-24B-A2B
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + PERIOD * 9 + ["full_attention",
                                                    "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
CUTS = ["num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
WHY = ("1 x 8192 real tokens, host-fed: LFM2 rank 0 of 8 (dense, attn, 3 "
       "conv layers): short conv, GQA 32/8 flash at d_head 64, 8 of 64 "
       "experts at 1/8 of their deployed rows; dense layer 44% of FLOPs")
NEW_READERS = ["device_ms_per_step.short_conv", "short_conv_roofline_share",
               "device_ms_per_step.held_experts",
               "held_expert_matmul_roofline_share",
               "flash_gqa_roofline_share", "held_expert_row_share"]


def real():
    return bench_run.load_cell("lfm2-8k", (BENCH,))


def reader(name):
    return bench_run.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"))


def load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_configuration_holds_the_published_numbers_and_exactly_five_cuts():
    _, config, _ = real()
    assert len(CATALOG["layer_types"]) == 40
    assert CATALOG["layer_types"].count("full_attention") == 10
    differs = [k for k, v in CATALOG.items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(CUTS) and config["reduced"] == CUTS
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 8192)
    # the dense layer, then published layers 2-5: one whole period
    assert config["layer_types"] == ["conv"] + PERIOD
    assert config["layer_types"][1:] == CATALOG["layer_types"][2:6]
    assert config["published"]["num_experts"] == 64
    assert config["published"]["vocab_size"] == 65536
    assert (config["expert_parallel_size"], config["expert_parallel_rank"],
            config["sequence_length"]) == (8, 0, 8192)
    assert "8 chips share each layer" in config["deployment"]
    for cut in ("64 -> 8", "65536 -> 8192", "2 -> 1", "40 -> 5"):
        assert cut in config["reduced_why"]
    bj = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bj["configs"] if c["name"] == "lfm2-24b-a2b"][0]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert entry["reduced"] == CUTS
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["z_loss_weight"],
            t["expert_bias_update_rate"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, 0.0, 0.001)
    assert {"d_head", "embedding", "selection bias", "norm_topk_prob",
            "router update", "weights", "training", "sequence_length",
            "recomputation"} <= set(config["assumed"])


def test_parameters_by_hand():
    """486.0 M parameters: 5.83 GB of float32 master weights and two
    Adam moments, 7.78 GB with a float32 gradient beside them."""
    d, dff, h, kv, vocab = 2048, 11776, 1536, 512, 8192
    conv = d * 3 * d + d * 3 + d * d                # in, filter, out
    attn = 2 * d * d + 2 * d * kv + 2 * 64          # q, o, k, v, qk scales
    experts = d * 64 + 8 * 3 * d * h                # router, 8 held
    norms = 2 * d
    dense = conv + 3 * d * dff + norms
    attn_layer = attn + experts + norms
    conv_layer = conv + experts + norms
    total = 2 * vocab * d + dense + attn_layer + 3 * conv_layer + d
    assert total == 486062208
    assert round(12 * total / 1e9, 2) == 5.83
    assert round(16 * total / 1e9, 2) == 7.78


def test_cell_is_the_issues_letter_for_letter_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"], cell["why"]) == (
        "lfm2-24b-a2b", "b1-len8192-host", 1, None, 1, 8192, "host", 8, WHY)
    assert len(WHY) == 191
    bj = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    # by name, not by place: the next cell is appended after this one
    assert "lfm2-8k" in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == "lfm2-8k"] == [{
        "name": "lfm2-8k", "config": "lfm2-24b-a2b",
        "traffic": "b1-len8192-host", "chips": 1, "why": WHY}]
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": 8192, "unit": "tokens/s"}}


def test_lfm2_train_flops_by_hand():
    cell, config, family = real()
    d, t = 2048, 8192
    conv = 2 * d * 3 * d + 2 * d * d                # in and out projections
    attn = 2 * 2 * d * d + 2 * 2 * d * 512 + 2 * 2 * t * d // 2
    dense = 3 * 2 * d * 11776
    router = 2 * d * 64
    held = 3 * 2 * d * 1536 // 2                    # 4 x 8 / 64 = 0.5 expert
    head = 2 * d * 8192
    assert (conv, attn, dense, router, held, head) == (
        33554432, 54525952, 144703488, 262144, 9437184, 33554432)
    # the issue's rows: dense layer, attention layer, three conv layers
    assert conv + dense == 178257920
    assert attn + router + held == 64225280
    assert 3 * (conv + router + held) == 129761280
    assert family.forward_flops_per_token(config, t) == {
        "conv": 4 * conv, "full_attention": attn, "dense_ffn": dense,
        "router": 4 * router, "experts": 4 * held, "head": head}
    per_token = 4 * conv + attn + dense + 4 * (router + held) + head
    assert per_token == 405798912           # 406 MFLOP forward, one token
    step = 3 * per_token * 8192
    assert step == 9972914061312            # 9.97 TFLOP a step
    assert family.train_flops(config, cell) == pytest.approx(step,
                                                             rel=1e-12)
    assert (conv + dense) / per_token == pytest.approx(0.439, abs=1e-3)


def test_make_batch_draws_from_the_slice_shifted_by_one_and_seeded():
    cell, config, family = real()
    a = family.make_batch(config, cell, np.random.default_rng(2**31 + 5))
    b = family.make_batch(config, cell, np.random.default_rng(2**31 + 5))
    assert a["tokens"].shape == a["labels"].shape == (1, 8192)
    assert a["tokens"].dtype == np.int64
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].min() >= 1 and a["tokens"].max() < 8192
    assert (a["tokens"] <= 10).mean() > 0.04
    with pytest.raises(ValueError, match="not the sequence_length"):
        family.make_batch(config, dict(cell, length=256),
                          np.random.default_rng(0))


def test_count_functions_by_hand():
    cell, config, _ = real()
    t, d = 8192, 2048
    # 4 conv layers x (BCu, out | BCu, dout, dBCu) x bf16
    assert counts.short_conv_bytes(config, cell) == 4 * 11 * t * d * 2 \
        == 1476395008
    flops, nbytes = counts.flash_gqa_cost(config, cell)
    assert flops == 7 * 32 * t * t * 64 == 962072674304
    assert nbytes == 6 * t * (2048 + 512) * 2 == 251658240
    rows = 4096.0                           # the uniform expectation
    flops, nbytes = counts.held_expert_matmul_cost(config, cell, rows)
    assert flops == 4 * 9 * 2 * 4096 * 2048 * 1536 == 927712935936
    assert nbytes == 4 * 9 * 2 * (4096 * 2048 + 4096 * 1536
                                  + 8 * 2048 * 1536)
    assert (counts.layers_of(config, "conv"),
            counts.layers_of(config, "full_attention"),
            counts.routed_layers(config)) == (4, 1, 4)


def test_new_readers_match_benchmark_json_and_read_none_without_a_trace():
    bj = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bj["per_layer"]}
    assert set(NEW_READERS) <= set(listed)
    cell, config, _ = real()
    no_trace = {"cell": cell, "config": config, "trace": None, "steps": 5}
    for name in NEW_READERS:
        module = reader(name)
        assert module.META["cells"] == ["lfm2-8k"] == listed[name][
            "workloads"]
        assert module.META["moves"] == "mfu"
        if module.META["source"] == "device_trace":
            assert module.compute(no_trace) is None
    # every all-cell reader is the cell's too
    readers = bench_run.layer_readers("lfm2-8k", (BENCH,))
    everywhere = {m["name"] for m in bj["per_layer"] if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert "lfm2-8k" in readers[name].META["cells"]


def rows_fixture():
    """Rows as `observe/trace.op_rows` gives them for 2 traced steps."""
    def row(instruction, bucket, self_s, op_type=None, op_name="",
            kernel=None):
        return {"module": "jit_step(1)", "instruction": instruction,
                "bucket": bucket, "self_s": self_s, "calls": 2,
                "op_type": op_type, "op_name": op_name, "phase": "forward",
                "flops": 0.0, "kernel": kernel}

    return [
        row("fusion.1", "matmul", 0.020, "mul"),
        row("fusion.2", "elementwise", 0.003, "short_conv"),
        row("fusion.3", "elementwise", 0.005, "short_conv",
            "jit(step)/transpose(jvp(short_conv:7))/mul"),
        row("fusion.4", "elementwise", 0.004, "moe_dropless"),
        row("sort.1", "elementwise", 0.002, "moe_dropless"),
        row("ragged-dot-none.1", "custom_call", 0.016,
            op_name="ragged-dot-none", kernel="ragged_dot"),
        row("ragged-dot-metadata", "custom_call", 0.001,
            op_name="ragged-dot-metadata", kernel="ragged_dot_metadata"),
        row("custom-call.3", "custom_call", 0.012, "flash_attention",
            "jit(step)/flash_attention:9/pallas_flash_gqa_fwd",
            kernel="flash_gqa_fwd"),
        row("custom-call.4", "custom_call", 0.028, "flash_attention",
            "jit(step)/transpose(jvp(flash_attention:9))/pallas_flash_gqa_dkv",
            kernel="flash_gqa_dkv"),
        # the other family's kernel is not this reader's
        row("custom-call.5", "custom_call", 0.050, "flash_attention",
            "jit(step)/flash_attention:9/pallas_flash_fwd",
            kernel="flash_fwd"),
    ]


@pytest.fixture
def traced(monkeypatch):
    cell, config, _ = real()
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows_fixture())
    monkeypatch.setattr(kernel_counts, "peaks", lambda: {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(counts, "held_rows_per_layer_step",
                        lambda config, cell: 4096.0)
    return {"cell": cell, "config": config, "steps": 2,
            "trace": {"path": "x", "chip0": {"lo": 0.0, "hi": 1.0,
                                             "steps": 2}}}


def test_readers_on_a_fixture(traced):
    assert reader("device_ms_per_step.short_conv").compute(
        traced) == pytest.approx(4.0)            # (3 + 5) ms / 2 steps
    nbytes = counts.short_conv_bytes(traced["config"], traced["cell"])
    assert reader("short_conv_roofline_share").compute(
        traced) == pytest.approx(100 * (nbytes / 819e9) / 0.004)
    # the op's rows (4 + 2) and its grouped matmuls (16), not the
    # metadata helper
    assert reader("device_ms_per_step.held_experts").compute(
        traced) == pytest.approx(11.0)
    flops, _ = counts.held_expert_matmul_cost(
        traced["config"], traced["cell"], 4096.0)
    assert reader("held_expert_matmul_roofline_share").compute(
        traced) == pytest.approx(100 * (flops / 197e12) / 0.008)
    flops, _ = counts.flash_gqa_cost(traced["config"], traced["cell"])
    assert reader("flash_gqa_roofline_share").compute(
        traced) == pytest.approx(100 * (flops / 197e12) / 0.020)
    for name in NEW_READERS[:5]:
        assert 0 < reader(name).compute(traced) < 100 or name.startswith(
            "device_ms")


def test_a_step_without_the_new_ops_reads_zero_or_nothing(traced,
                                                          monkeypatch):
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows_fixture()[:1])
    assert reader("device_ms_per_step.short_conv").compute(traced) == 0.0
    assert reader("device_ms_per_step.held_experts").compute(traced) == 0.0
    assert reader("short_conv_roofline_share").compute(traced) is None
    assert reader("flash_gqa_roofline_share").compute(traced) is None
    assert reader("held_expert_matmul_roofline_share").compute(
        traced) is None


def test_readers_give_nothing_on_a_program_without_the_counters(
        traced, monkeypatch):
    """The parent's `observe/routing.py` has no `held_row_share`: the
    two readers that need it leave their metric out and do not
    raise."""
    from paddle_tpu.observe import routing

    monkeypatch.undo()
    monkeypatch.delattr(routing, "held_row_share")
    cell, config, _ = real()
    run = {"cell": cell, "config": config, "trace": None, "steps": 2}
    assert reader("held_expert_row_share").compute(run) is None
    assert counts.held_rows_per_layer_step(config, cell) is None
    assert reader("held_expert_matmul_roofline_share").compute(run) is None


def test_toy_lfm2_cell_runs_the_harness_and_counts_its_share(capfd):
    result = bench_run.run_cell("tiny-lfm2-host", 2**31 + 11, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left
    # out; the counters report (through
    # fixtures/layer_metrics/tiny_held_row_share.py, read while the
    # cell's scope is alive: 2 of 8 experts held, top-2)
    assert set(result["metrics"]) == {"dispatch_ms.train",
                                      "compiles_in_window",
                                      "tiny_held_row_share"}
    assert 0.0 < result["metrics"]["tiny_held_row_share"]["value"] < 100.0


def test_both_copies_of_the_reference_give_the_same_numbers():
    import jax.numpy as jnp

    from paddle_tpu.models import decoder_reference as package_copy

    bench_copy = load("reference_lfm2")
    cfg = {"hidden_size": 128, "num_attention_heads": 4,
           "num_key_value_heads": 2, "norm_eps": 1e-5,
           "rope_parameters": {"rope_theta": 1000000},
           "num_experts_per_tok": 2, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0, "num_hidden_layers": 2,
           "layer_types": ["conv", "full_attention"],
           "num_dense_layers": 1, "expert_parallel_rank": 1}
    rng = np.random.default_rng(0)
    d, v = 128, 50
    shapes = [(v, d),
              (d,), (d, 3 * d), (d, 3), (d, d), (d,), (d, 24), (d, 24),
              (24, d),
              (d,), (d, d), (32,), (d, 64), (32,), (d, 64), (d, d), (d,),
              (d, 8), (2, d, 16), (2, 16, d), (2, d, 16),
              (d,), (d, v)]
    arrays = [rng.normal(size=s).astype(np.float32) * 0.2 for s in shapes]
    bias = [rng.normal(size=8).astype(np.float32) * 0.1]
    ids = rng.integers(0, v, size=(2, 9))
    out = []
    for module, prefix in ((package_copy, "lfm2_"), (bench_copy, "")):
        params = getattr(module, prefix + "params_from_list")(
            arrays, cfg, bias)
        (total, parts), grads = getattr(module, prefix + "loss_and_grads")(
            params, jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:]), cfg)
        blocked = getattr(module, prefix + "forward")(
            params, jnp.asarray(ids[:, :-1]), cfg, 3)
        out.append((float(total), np.asarray(parts["logits"]),
                    np.asarray(grads["layers"][1]["w2"]),
                    np.asarray(grads["layers"][0]["filter"]),
                    np.asarray(parts["counts"][0]),
                    np.asarray(blocked["logits"])))
    assert out[0][0] == out[1][0]
    for a, b in zip(out[0][1:], out[1][1:]):
        np.testing.assert_array_equal(a, b)
    # scores computed 3 query rows at a time are the same attention
    np.testing.assert_allclose(out[0][5], out[0][1], rtol=1e-5, atol=1e-6)
    assert out[0][4].shape == (2,)          # rank 1's two experts


def test_parity_script_compares_where_every_layers_experts_agree():
    parity = load("lfm2_parity")
    n, last, layers = 300, parity.LAST, 4
    experts = np.tile(np.arange(4), (layers, n, 1))
    want = {"logits": np.zeros((last, 5), np.float32), "loss": 2.0,
            "experts": experts, "counts": np.full((layers, 8), 37),
            "grad_names": ["embed", "layer1.router", "head"],
            "grads": [np.ones((3, 2), np.float32),
                      np.zeros((2, 2), np.float32),
                      np.full((4,), 2.0, np.float32)]}
    got = dict(want, logits=want["logits"].copy(), loss=2.001,
               experts=experts.copy(),
               grads=[np.ones((3, 2), np.float32),
                      np.zeros((2, 2), np.float32),
                      np.full((4,), 2.2, np.float32)])
    got["logits"][-1, 0] = 0.5            # a token routed elsewhere,
    got["experts"][2, -1, 0] = 63         # in one layer of the four
    got["logits"][3, 1] = 0.01
    c = parity.compare(got, want)
    assert c["logit_err_max"] == pytest.approx(0.01)
    assert c["logit_err_all_max"] == pytest.approx(0.5)
    assert c["flipped_share"] == pytest.approx(1 / (layers * n))
    assert c["flipped_in_tail"] == 1 and c["counts_equal"]
    assert c["loss_err"] == pytest.approx(0.001)
    assert c["held_rows"] == [296] * 4
    # gradients: the worst leaf's error over its norm; a leaf without
    # a gradient on the reference's side must have none on the system's
    assert c["grad_err_worst"] == pytest.approx(0.1, rel=1e-5)
    assert c["grad_err_worst_leaf"] == "head"
    assert c["grad_err"]["embed"] == 0.0
    assert c["grad_dead_leaves"] == ["layer1.router"]
    got["grads"][1] = np.full((2, 2), 1e-9, np.float32)
    assert parity.compare(got, want)["grad_err_worst"] == float("inf")
    # and end to end at a toy size on the CPU: float32 inside its limits
    _, config, family = bench_run.load_cell("tiny-lfm2-host",
                                            (BENCH, FIXTURES))
    parity.LAST, parity.Q_BLOCK, parity.GRAD_Q_BLOCK = 16, 8, 8
    r = parity.check_seed(config, family, 2**31 + 3)
    assert r["checks"]["f32_logits"] and r["checks"]["f32_loss"]
    assert r["checks"]["f32_routing"] and r["checks"]["f32_held_counts"]
    assert r["checks"]["share_is_a_share"]
    assert r["checks"]["grads_are_compared"] and r["checks"]["f32_grads"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-4
    assert all(n.endswith(".router")
               for n in r["f32"]["grad_dead_leaves"])
    assert {"embed", "head", "final_norm"} <= set(r["f32"]["grad_err"])
    assert r["f32"]["counts_equal"] and r["f32"]["flipped_share"] == 0.0
