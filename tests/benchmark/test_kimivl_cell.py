"""The `kimivl-8k` cell (ISSUE 73): its configuration against the
catalog row, the family's map, the parameter count, the collator's
invariants, the counts by part, the new readers against BENCHMARK.json
and a fixture trace, the toy cell through the harness and the parity
script at the toy's size, all on the CPU.  What the chip says is
PERF.md's.
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
import kernel_counts_kimi_vl as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
CELL, CONFIG = "kimivl-8k", "kimi-vl-a3b"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/"
          "blob/main/config.json")
CATALOG = {      # the catalog row's `config`, Kimi-VL-A3B-Instruct
    "vocab_size": 163840, "max_position_embeddings": 131072,
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "n_shared_experts": 2,
    "n_routed_experts": 64, "ep_size": 1, "routed_scaling_factor": 2.446,
    "kv_lora_rank": 512, "q_lora_rank": None, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "qk_nope_head_dim": 128, "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6,
    "moe_layer_freq": 1, "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False}
TOWER = {"model_type": "moonvit", "hidden_size": 1152,
         "num_hidden_layers": 27, "num_attention_heads": 16,
         "intermediate_size": 4304, "patch_size": 14,
         "init_pos_emb_height": 64, "init_pos_emb_width": 64,
         "merge_kernel_size": [2, 2]}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "vision_config"]
NEW_READERS = {
    "device_ms_per_step.vision_tower": "device_trace",
    "device_ms_per_step.vision_attention": "device_trace",
    "device_ms_per_step.vision_projector": "device_trace",
    "flash_segment_roofline_share": "device_trace",
    "flash_segment_tile_visit_ratio": "program_counter",
    "flash_mla_16h_roofline_share": "device_trace",
    "held_expert_row_share_w1408": "program_counter",
    "device_ms_per_step.latent_attention_16h": "device_trace",
    "device_ms_per_step.routed_ffn_w1408": "device_trace",
    "held_expert_matmul_w1408_roofline_share": "device_trace"}
T, P, ROWS, V, IMAGES = 8192, 24576, 6144, 20480, 16
PAIRS = 61341696
PARAMETERS = 726479616


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
    assert sorted(differs + ["vision_config"]) == sorted(REDUCED) \
        == sorted(config["reduced"])
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, V)
    assert V == 163840 // 8 and 8 * config["expert_parallel_size"] == 64
    # inside the tower's group: the depth, and no width
    group = config["vision_config"]
    assert {k for k in TOWER if group[k] != TOWER[k]} == {
        "num_hidden_layers"}
    assert group["num_hidden_layers"] == 8
    assert group["hidden_size"] // group["num_attention_heads"] == 72
    assert config["published"] == {
        "num_hidden_layers": 27, "n_routed_experts": 64,
        "vocab_size": 163840, "vision_config": {"num_hidden_layers": 27},
        "media_placeholder_token_id": 163605}
    assert (config["media_placeholder_token_id"], config["in_token_limit"],
            config["sequence_length"], config["patch_rows"]) == (
        0, 4096, T, P)
    assert "726.5 M parameters = 8.72 GB of state" in config["reduced_why"]
    assert "58 %" in config["reduced_why"]
    assert "TOWER AND THE PROJECTOR ARE WHOLE" in config["deployment"]
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    t = config["training"]
    assert (t["learning_rate"], t["weight_decay"], t["warmup_steps"],
            t["clip_norm"], t["aux_loss_weight"],
            t["expert_bias_update_rate"], t["recompute"], t["use_amp"]) == (
        4e-4, 0.1, 2000, 1.0, 0.0, 0.01, "layer", True)
    assert {"vision_config", "in_token_limit", "tower equations",
            "patch order", "media_placeholder_token_id", "rope_interleave",
            "selection bias", "loss", "pixels", "training",
            "recomputation"} <= set(config["assumed"])
    assert "float32" in config["precision"]


def test_the_family_maps_the_published_keys_onto_the_builders():
    _, config, family = real()
    args = family.architecture(config)
    assert (args["num_experts"], args["num_dense_layers"], args["router"],
            args["use_expert_bias"], args["norm_topk_eps"],
            args["loss_weights"], args["media_placeholder_token_id"]) == (
        8, 1, "sigmoid", True, 1e-20, True, 0)
    assert args["q_lora_rank"] is None and args["rope_interleave"] is True
    assert "mla_use_nope" not in args       # the rotary lanes turn
    tower = family.tower_architecture(config)
    assert (tower["hidden_size"], tower["num_attention_heads"],
            tower["intermediate_size"], tower["text_hidden_size"],
            tower["patch_rows"], tower["in_token_limit"]) == (
        1152, 16, 4304, 2048, P, 4096)


def test_parameters_by_hand():
    tower_layer = 4 * (1152 * 1152 + 1152) + 2 * 2 * 1152 \
        + 2 * 1152 * 4304 + 4304 + 1152
    assert tower_layer == 15239504
    projector = 2 * 1152 + 4608 * 4608 + 4608 + 4608 * 2048 + 2048
    assert projector == 30679808
    tower = (8 * tower_layer + 588 * 1152 + 1152 + 64 * 64 * 1152
             + 2 * 1152 + projector)
    attention = 2048 + 2048 * 16 * 192 + 2048 * 576 + 512 \
        + 512 * 16 * 256 + 2048 * 2048
    dense = attention + 2048 + 3 * 2048 * 11264
    routed = attention + 2048 + 2048 * 64 + 3 * 2048 * 2816 \
        + 8 * 3 * 2048 * 1408
    decoder = dense + 4 * routed + 2 * V * 2048 + 2048
    assert tower + decoder == PARAMETERS


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (CONFIG, "b1-len8192-img6144-host", 1, None, 1,
                              T, "host", 4)
    assert len(cell["why"]) <= 200 and "58 MB feed" in cell["why"]
    assert [(g["patches"], g["count"]) for g in cell["images"]] == [
        (4096, 2), (2304, 4), (1024, 6), (256, 4)]
    for group in cell["images"]:
        assert all(h * w == group["patches"] and h % 2 == w % 2 == 0
                   for h, w in group["grids"])
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert tokens["workloads"][-1] == CELL
    assert [w for w in bj["workloads"] if w["name"] == CELL] == [{
        "name": CELL, "config": CONFIG, "traffic": cell["traffic"],
        "chips": 1, "why": cell["why"]}]
    assert len(bj["workloads"]) >= 15
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": T, "unit": "tokens/s"}}


def test_train_flops_by_part():
    cell, config, family = real()
    parts = family.forward_flops(config, cell)
    assert family.allowed_pairs(cell) == counts.allowed_pairs(cell) == PAIRS
    assert parts["tower_projections"] == 8 * 2 * P * (
        4 * 1152 ** 2 + 2 * 1152 * 4304)
    assert parts["tower_attention"] == 8 * PAIRS * 16 * 288
    assert parts["patch_embedding"] == 2 * P * 588 * 1152
    assert parts["projector"] == 2 * ROWS * (4608 ** 2 + 4608 * 2048)
    position = sum(v for k, v in parts.items()
                   if not k.startswith(("tower", "patch", "projector"))) / T
    assert position == pytest.approx(760.6e6, rel=1e-3)
    assert parts["experts"] == T * 4 * 0.75 * 6 * 2048 * 1408
    assert parts["shared_experts"] == T * 4 * 2 * 6 * 2048 * 1408
    total = sum(parts.values())
    assert total == pytest.approx(14.89e12, rel=1e-3)
    assert family.train_flops(config, cell) == 3 * total
    tower = sum(v for k, v in parts.items()
                if k.startswith(("tower", "patch", "projector")))
    assert tower / total == pytest.approx(0.581, abs=1e-3)
    assert parts["tower_attention"] / tower == pytest.approx(0.261, abs=2e-3)


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    flops, nbytes = counts.flash_segment_cost(config, cell)
    assert flops == 8 * 7 * 2 * PAIRS * 16 * 72
    assert nbytes == 8 * 12 * P * 1152 * 2
    assert flops / 197e12 > nbytes / 819e9          # the products bound it
    assert counts.patches(cell) == P
    flops, nbytes = counts.flash_mla_cost(config, cell)
    assert flops == 5 * (320 + 640 + 512) * 16 * T * T
    assert counts.SEGMENT_KERNEL_NAMES == ("flash_segment_fwd",
                                           "flash_segment_bwd")


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_make_batch_holds_the_cells_multiset_every_batch_and_seed(seed):
    cell, config, family = real()
    rng = np.random.default_rng(seed)
    for _ in range(2):
        b = family.make_batch(config, cell, rng)
        assert {k: (v.shape, str(v.dtype)) for k, v in b.items()} == {
            "tokens": ((1, T), "int64"), "labels": ((1, T), "int64"),
            "loss_weights": ((1, T), "float32"),
            "pixel_values": ((1, P, 588), "float32"),
            "patch_segments": ((1, P), "int32"),
            "patch_yx": ((1, P, 2), "int32"),
            "pos_taps": ((1, P, 16), "int32"),
            "pos_weights": ((1, P, 16), "float32")}
        tokens, labels = b["tokens"][0], b["labels"][0]
        assert (tokens == 0).sum() == ROWS
        np.testing.assert_array_equal(tokens[1:], labels[:-1])
        np.testing.assert_array_equal(b["loss_weights"][0] == 0, labels == 0)
        assert labels[-1] != 0 and tokens[0] != 0
        assert 1 <= tokens[tokens != 0].min() and tokens.max() < V
        seg = b["patch_segments"][0]
        assert sorted(np.bincount(seg).tolist()) == sorted(
            family.image_patch_counts(cell))
        assert (np.diff(seg) >= 0).all() and seg.min() == 0
        # the runs of placeholders are the images' rows, in the order the
        # images lie on the row axis, a text token before each and after
        # the last
        runs = np.flatnonzero(np.diff(np.r_[0, tokens == 0, 0]))
        assert (runs[1::2] - runs[::2]).tolist() == (
            np.bincount(seg) // 4).tolist()
        assert len(runs) == 2 * IMAGES and runs[0] >= 1 and runs[-1] < T
        # a 2 x 2 block's four patches are consecutive
        yx = b["patch_yx"][0].reshape(-1, 4, 2)
        assert (yx[:, :, 0] // 2 == yx[:, :1, 0] // 2).all()
        assert (yx[:, :, 1] // 2 == yx[:, :1, 1] // 2).all()
        np.testing.assert_allclose(b["pos_weights"][0].sum(axis=1), 1.0,
                                   atol=1e-5)
        assert b["pos_taps"].min() >= 0 and b["pos_taps"].max() < 64 * 64
    with pytest.raises(ValueError, match="sequence_length"):
        family.make_batch(config, dict(cell, length=4096), rng)


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
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert CELL in readers[name].META["cells"]
    for other in ("joyai-8k", "kimilinear-8k"):
        assert not set(NEW_READERS) & set(
            bench_run.layer_readers(other, (BENCH,)))


def rows_fixture():
    """Rows as `observe/trace.op_rows` gives them for 2 traced steps."""
    def row(instruction, bucket, self_s, scope="", op_type=None,
            kernel=None):
        return {"module": "jit_step(1)", "instruction": instruction,
                "bucket": bucket, "self_s": self_s, "calls": 2,
                "op_type": op_type, "name_scope": scope, "op_name": "",
                "phase": "backward", "flops": 0.0, "kernel": kernel,
                "joined": True}

    tower, attention = "vision_tower", "vision_tower/vision_attention"
    return [
        row("fusion.1", "matmul", 0.100, tower, "mul"),
        row("fusion.2", "elementwise", 0.020, "checkpoint/" + tower,
            "layer_norm"),
        row("fusion.3", "matmul", 0.060, attention, "mul"),
        row("fusion.4", "elementwise", 0.010, attention, "rope"),
        row("custom-call.1", "custom_call", 0.070, attention,
            "segment_attention", "flash_segment_fwd"),
        row("custom-call.2", "custom_call", 0.120, attention,
            "segment_attention", "flash_segment_bwd"),
        row("fusion.5", "matmul", 0.030, "vision_projector", "mul"),
        row("fusion.6", "matmul", 0.040, "latent_attention", "mul"),
        row("custom-call.3", "custom_call", 0.060, "latent_attention",
            "latent_attention", "flash_mla_fwd"),
        row("custom-call.4", "custom_call", 0.090, "latent_attention",
            "latent_attention", "flash_mla_dkv"),
        row("fusion.9", "matmul", 0.016, "shared_expert", "mul"),
        row("fusion.10", "elementwise", 0.008, "", "moe_dropless"),
        row("custom-call.5", "custom_call", 0.024, "", "moe_dropless",
            "ragged_dot"),
        row("fusion.7", "elementwise", 0.002, "image_merge", "image_merge"),
        row("fusion.8", "elementwise", 0.005, "", "adam"),
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
    assert reader("device_ms_per_step.vision_tower").compute(traced) \
        == pytest.approx((100 + 20 + 60 + 10 + 70 + 120) / 2)
    assert reader("device_ms_per_step.vision_attention").compute(traced) \
        == pytest.approx((60 + 10 + 70 + 120) / 2)
    assert reader("device_ms_per_step.vision_projector").compute(traced) \
        == pytest.approx(30 / 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(kernel_counts, "peaks", lambda: peak)
    flops, _ = counts.flash_segment_cost(traced["config"], traced["cell"])
    want = 100 * 1e3 * flops / 197e12 / ((70 + 120) / 2)
    assert reader("flash_segment_roofline_share").compute(traced) \
        == pytest.approx(want)
    assert 0 < want < 100
    flops, _ = counts.flash_mla_cost(traced["config"], traced["cell"])
    want = 100 * 1e3 * flops / 197e12 / ((60 + 90) / 2)
    assert reader("flash_mla_16h_roofline_share").compute(traced) \
        == pytest.approx(want)
    assert 0 < want < 100
    # the decoder's parts: latent attention's scope with its kernels; the
    # expert op's rows, its grouped matmuls and the shared expert's scope
    assert reader("device_ms_per_step.latent_attention_16h").compute(
        traced) == pytest.approx((40 + 60 + 90) / 2)
    assert reader("device_ms_per_step.routed_ffn_w1408").compute(traced) \
        == pytest.approx((16 + 8 + 24) / 2)
    from paddle_tpu.observe import routing
    matmuls = reader("held_expert_matmul_w1408_roofline_share")
    monkeypatch.setattr(routing, "held_row_share", lambda: None)
    assert matmuls.compute(traced) is None          # no counters: nothing
    monkeypatch.setattr(routing, "held_row_share", lambda: 0.125)
    rows = 0.125 * T * 6
    flops, nbytes = counts.expert_matmul_cost(traced["config"],
                                              traced["cell"], rows)
    assert flops == 4 * 9 * 2 * rows * 2048 * 1408
    assert nbytes == 4 * 9 * 2 * (rows * (2048 + 1408) + 8 * 2048 * 1408)
    want = 100 * 1e3 * max(flops / 197e12, nbytes / 819e9) / (24 / 2)
    assert matmuls.compute(traced) == pytest.approx(want)
    assert 0 < want < 100

    # a program whose rows carry no name scope and no such kernel (the
    # parent's) reads nothing and does not raise
    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture() if not (r["kernel"] or "").startswith(
                    ("flash_segment", "flash_mla", "ragged_dot"))]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    for name, source in NEW_READERS.items():
        if source == "device_trace":
            assert reader(name).compute(traced) is None, name


def test_the_counters_read_the_programs_own(monkeypatch):
    from paddle_tpu.observe import routing

    ratio = reader("flash_segment_tile_visit_ratio")
    monkeypatch.setattr(routing, "segment_tile_visits", lambda: (1408, 9216))
    assert ratio.compute({}) == pytest.approx(1408 / 9216)
    monkeypatch.setattr(routing, "segment_tile_visits", lambda: None)
    assert ratio.compute({}) is None        # the XLA lowering: no visit
    # a program from before the counters: nothing to read, no raise
    monkeypatch.delattr(routing, "segment_tile_visits")
    assert ratio.compute({}) is None
    share = reader("held_expert_row_share_w1408")
    monkeypatch.setattr(routing, "held_row_share", lambda: 0.125)
    assert share.compute({}) == 12.5
    monkeypatch.setattr(routing, "held_row_share", lambda: None)
    assert share.compute({}) is None


def test_toy_cell_runs_the_harness(capfd):
    from paddle_tpu.observe import routing

    result = bench_run.run_cell("tiny-kimi-vl-host", 2**31 + 11, 5.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) >= {"dispatch_ms.train",
                                      "compiles_in_window"}
    assert '"loss_fell": true' in capfd.readouterr().out
    # 128 packed rows run the XLA lowering: the layers' device counters
    # exist and hold nothing, so the ratio's reader leaves its metric out
    assert routing.segment_tile_visits() is None


def _toy_parity(monkeypatch):
    parity = load("kimi_vl_parity")
    cell, config, family = bench_run.load_cell("tiny-kimi-vl-host",
                                               (BENCH, FIXTURES))
    monkeypatch.setattr(parity.base, "LAST", 16)
    monkeypatch.setattr(parity.base, "Q_BLOCK", 16)
    monkeypatch.setattr(parity.base, "GRAD_Q_BLOCK", 16)
    return parity, (config, family, cell, 2**31 + 9)


def test_parity_script_compares_logits_routing_and_every_leaf(monkeypatch):
    parity, args = _toy_parity(monkeypatch)
    r = parity.check_seed(*args)
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_loss"]
    assert checks["f32_routing"] and checks["f32_held_counts"]
    assert checks["share_is_a_share"] and checks["grads_are_compared"]
    assert checks["f32_grads"], r["f32"]["grad_err_worst_leaf"]
    names = parity.reference.system_names(args[0])
    assert len(r["f32"]["grad_err"]) == len(names)
    assert r["f32"]["grad_err_worst_tower_leaf"].startswith("vision.")
    assert r["f32"]["grad_err_worst_tower"] < 1e-5
    assert r["f32"]["grad_dead_leaves"] == ["layer1.router"]


def test_parity_scripts_control_sees_a_bfloat16_rotary_product(monkeypatch):
    """The control: the tower's rotary cos and sin rounded to bfloat16,
    all else float32, moves the tower's worst gradient leaf from under
    1e-5 (above) to over 1e-3."""
    from paddle_tpu.ops import decoder as ops

    parity, args = _toy_parity(monkeypatch)
    # (monkeypatch puts the module's own function back after the test)
    monkeypatch.setattr(ops, "_cos_sin_two_axes", ops._cos_sin_two_axes)
    parity.rotary_in_bfloat16()
    control = parity.check_seed(*args, control=True)
    assert control["f32"]["grad_err_worst_tower"] > 1e-3
    assert "bf16" not in control
