"""The `joyai-llm-flash` configuration, its family, the `joyai-8k` cell
and the readers PR 32 adds, on the CPU: the files and BENCHMARK.json
agree (entries looked up BY NAME: the next cell is appended after this
one), the configuration holds the catalog's numbers and exactly the
three cuts, the family's map onto the builder, `train_flops` and the
kernel counts against hand counts, each reader on a fixture and without
a trace, the parity script's arithmetic at a toy size, and a toy cell
through `run_cell`.  No number from here is a speed.
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
import kernel_counts_joyai as counts  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
SOURCE = ("https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
          "config.json")
CATALOG = {      # the catalog row's `config`, JoyAI-LLM-Flash
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}
CUTS = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_READERS = ["device_ms_per_step.latent_attention",
               "flash_mla_roofline_share", "device_ms_per_step.mtp",
               "device_ms_per_step.routed_ffn"]
T, HEADS = 8192, 32


def real():
    return bench_run.load_cell("joyai-8k", (BENCH,))


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


def test_configuration_holds_the_published_numbers_and_exactly_three_cuts():
    _, config, _ = real()
    differs = [k for k, v in CATALOG.items() if config.get(k, "absent") != v]
    assert sorted(differs) == sorted(CUTS) and config["reduced"] == CUTS
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 16160)
    assert config["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert 129280 // 8 == 16160 and 256 // 32 == 8        # the floors
    assert (config["expert_parallel_size"], config["expert_parallel_rank"],
            config["sequence_length"]) == (32, 0, 8192)
    assert "32 chips share each layer" in config["deployment"]
    assert "counts ONCE" in config["deployment"]
    for cut in ("256 -> 8", "129280 -> 16160", "40 -> 5"):
        assert cut in config["reduced_why"]
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == "joyai-llm-flash"][0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == CUTS
    assert entry["file"] == "benchmarks/configs/joyai-llm-flash.json"
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["z_loss_weight"], t["mtp_loss_weight"],
            t["expert_bias_update_rate"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.0, 0.0, 0.3, 0.01)
    # not ISSUE 32's 0.001: the reason is written where the rate is
    assert "NOT ISSUE 32's 0.001" in config["assumed"]["selection bias"]
    assert {"mtp_loss_weight", "mtp input", "selection bias",
            "norm_topk_prob", "embedding", "column order", "router update",
            "weights", "training", "sequence_length", "recomputation"} \
        <= set(config["assumed"])


def test_the_family_maps_the_published_keys_onto_the_builder():
    """Nothing renamed but what the module's docstring lists, and a
    value the builder does not build raises."""
    _, config, family = real()
    args = family.architecture(config)
    assert (args["num_experts"], args["num_dense_layers"], args["router"],
            args["use_expert_bias"], args["norm_topk_eps"]) == (
        8, 1, "sigmoid", True, 1e-20)
    for key in ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "rope_interleave",
                "n_shared_experts", "num_nextn_predict_layers",
                "routed_scaling_factor", "rope_theta", "rms_norm_eps"):
        assert args[key] == CATALOG[key], key
    assert not {"n_routed_experts", "first_k_dense_replace", "scoring_func",
                "topk_method", "n_group", "model_type", "head_dim",
                "ep_size"} & set(args)
    import inspect

    from paddle_tpu.models import decoder

    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    for key, value in (("n_group", 8), ("topk_group", 4),
                       ("moe_layer_freq", 2), ("hidden_act", "gelu"),
                       ("scoring_func", "tanh"), ("topk_method", "greedy")):
        with pytest.raises(NotImplementedError, match=key):
            family.architecture(dict(config, **{key: value}))
    with pytest.raises(ValueError, match="qk_head_dim"):
        family.architecture(dict(config, qk_head_dim=128))


def test_parameters_by_hand():
    """491.7 M parameters: 5.90 GB of float32 master weights and two
    Adam moments, 7.87 GB with a float32 gradient beside them."""
    d, vocab = 2048, 16160
    attention = (d * 1536 + 1536 + 1536 * HEADS * 192 + d * (512 + 64) + 512
                 + 512 * HEADS * 256 + HEADS * 128 * d)
    assert attention == 26347520
    norms = 2 * d
    dense = attention + 3 * d * 7168 + norms
    routed = attention + d * 256 + (8 + 1) * 3 * d * 768 + norms
    module = 2 * d + 2 * d * d + routed + d
    total = 2 * vocab * d + dense + 4 * routed + d + module
    assert total == 491696128
    assert round(12 * total / 1e9, 2) == 5.90
    assert round(16 * total / 1e9, 2) == 7.87


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (
        "joyai-llm-flash", "b1-len8192-host", 1, None, 1, 8192, "host", 8)
    assert len(cell["why"]) <= 200 and "far over its deployed share" \
        in cell["why"]
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert "joyai-8k" in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == "joyai-8k"] == [{
        "name": "joyai-8k", "config": "joyai-llm-flash",
        "traffic": "b1-len8192-host", "chips": 1, "why": cell["why"]}]
    assert len(bj["workloads"]) >= 6
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": 8192, "unit": "tokens/s"}}


def test_joyai_train_flops_by_hand():
    cell, config, family = real()
    d = 2048
    projections = 2 * 26345472               # the five, without the norms
    scores = 2 * T * HEADS * (192 + 128) / 2
    want = {"attention_projections": 6 * projections,
            "attention": 6 * scores,
            "dense_ffn": 3 * 2 * d * 7168,
            "router": 5 * 2 * d * 256,
            "shared_experts": 5 * 3 * 2 * d * 768,
            "experts": 5 * (8 / 32) * 3 * 2 * d * 768,
            "mtp_projection": 2 * 4096 * d,
            "head": 2 * 2 * d * 16160}
    got = family.forward_flops_per_token(config, T)
    assert got == pytest.approx(want)
    total = sum(got.values())
    assert total == pytest.approx(1.121e9, rel=1e-3)
    assert family.train_flops(config, cell) == pytest.approx(
        3 * total * T) == pytest.approx(27.5e12, rel=3e-3)
    share = {k: v / total for k, v in got.items()}
    # latent attention is 73% of the model FLOPs (scores and values 45,
    # projections 28), the two heads 12, the held experts 1
    assert round(100 * share["attention"]) == 45
    assert round(100 * share["attention_projections"]) == 28
    assert round(100 * share["head"]) == 12
    assert round(100 * share["experts"]) == 1


def test_make_batch_draws_three_shifted_views_of_8194_ids_from_the_slice():
    cell, config, family = real()
    a = family.make_batch(config, cell, np.random.default_rng(2**31 + 5))
    b = family.make_batch(config, cell, np.random.default_rng(2**31 + 5))
    assert sorted(a) == ["labels", "next_labels", "tokens"]
    for key in a:
        assert a[key].shape == (1, 8192) and a[key].dtype == np.int64
        assert 1 <= a[key].min() and a[key].max() < 16160
        np.testing.assert_array_equal(a[key], b[key])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    np.testing.assert_array_equal(a["labels"][:, 1:],
                                  a["next_labels"][:, :-1])
    with pytest.raises(ValueError, match="sequence_length"):
        family.make_batch(config, dict(cell, length=4096),
                          np.random.default_rng(0))


def test_kernel_counts_by_hand():
    cell, config, _ = real()
    flops, nbytes = counts.flash_mla_cost(config, cell)
    # 320 + 640 + 512 lanes a causal score pair, 32 heads, six blocks
    assert flops == 6 * 1472 * HEADS * T * T == pytest.approx(19.0e12,
                                                              rel=3e-3)
    wide, rotary, key = T * HEADS * 128, T * HEADS * 64, T * 64
    assert nbytes == 6 * 2 * (17 * wide + 4 * rotary + 4 * key)
    # the rotary key ONCE a kernel: repeated over the heads it would be
    # 32 x 64 wide in each of its four places
    assert nbytes < 6 * 2 * (17 * wide + 4 * rotary + 4 * HEADS * key)
    assert counts.blocks(config) == 6
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert kernel_counts.roofline_ms(flops, nbytes, peak) == pytest.approx(
        1e3 * flops / 197e12)             # compute-bound: 96 ms
    assert 96 < 1e3 * flops / 197e12 < 97


def test_new_readers_match_benchmark_json_and_read_none_without_a_trace():
    listed = {m["name"]: m for m in benchmark_json()["per_layer"]}
    assert set(NEW_READERS) <= set(listed)
    cell, config, _ = real()
    no_trace = {"cell": cell, "config": config, "trace": None, "steps": 5}
    for name in NEW_READERS:
        module = reader(name)
        assert module.META["cells"] == ["joyai-8k"] == listed[name][
            "workloads"]
        assert module.META["moves"] == "mfu" == listed[name]["moves"]
        assert module.META["unit"] == listed[name]["unit"]
        assert module.META["layer"] == listed[name]["layer"]
        assert module.META["source"] == "device_trace" == listed[name][
            "source"]
        assert module.compute(no_trace) is None
    # every all-cell reader is the cell's too, and no other cell's is
    readers = bench_run.layer_readers("joyai-8k", (BENCH,))
    everywhere = {m["name"] for m in benchmark_json()["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert "joyai-8k" in readers[name].META["cells"]
    assert not set(NEW_READERS) & set(
        bench_run.layer_readers("lfm2-8k", (BENCH,)))


def rows_fixture():
    """Rows as `observe/trace.op_rows` gives them for 2 traced steps."""
    def row(instruction, bucket, self_s, op_type=None, scope="",
            kernel=None, op_name=""):
        return {"module": "jit_step(1)", "instruction": instruction,
                "bucket": bucket, "self_s": self_s, "calls": 2,
                "op_type": op_type, "name_scope": scope, "op_name": op_name,
                "phase": "forward", "flops": 0.0, "kernel": kernel}

    return [
        row("fusion.1", "matmul", 0.020, "mul"),                # the head
        row("fusion.2", "matmul", 0.006, "mul", "latent_attention"),
        row("fusion.3", "elementwise", 0.002, "rope", "latent_attention"),
        row("custom-call.1", "custom_call", 0.030, "latent_attention",
            "latent_attention", "flash_mla_fwd"),
        row("custom-call.2", "custom_call", 0.050, "latent_attention",
            "latent_attention", "flash_mla_dkv"),
        row("custom-call.3", "custom_call", 0.010, "latent_attention",
            "mtp/latent_attention", "flash_mla_dq"),
        row("fusion.4", "elementwise", 0.004, "moe_dropless"),
        row("fusion.5", "elementwise", 0.002, "moe_dropless", "mtp"),
        row("ragged-dot-none.1", "custom_call", 0.016, kernel="ragged_dot",
            op_name="ragged-dot-none"),
        row("ragged-dot-metadata", "custom_call", 0.001,
            kernel="ragged_dot_metadata", op_name="ragged-dot-metadata"),
        row("fusion.6", "matmul", 0.008, "mul", "shared_expert"),
        row("fusion.7", "matmul", 0.004, "mul", "mtp/shared_expert"),
        row("fusion.8", "matmul", 0.012, "mul", "mtp"),     # eh, the head
        # another family's kernel is not this reader's
        row("custom-call.9", "custom_call", 0.070, "flash_attention",
            kernel="flash_gqa_fwd"),
    ]


@pytest.fixture
def traced(monkeypatch):
    cell, config, _ = real()
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows_fixture())
    monkeypatch.setattr(kernel_counts, "peaks", lambda: {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    return {"cell": cell, "config": config, "steps": 2,
            "trace": {"path": "x", "chip0": {"lo": 0.0, "hi": 1.0,
                                             "steps": 2}}}


def test_readers_on_a_fixture(traced):
    # projections, rope and the three kernels, the module's too
    assert reader("device_ms_per_step.latent_attention").compute(
        traced) == pytest.approx((6 + 2 + 30 + 50 + 10) / 2)
    # everything built under `mtp`; its grouped matmuls carry no scope
    assert reader("device_ms_per_step.mtp").compute(
        traced) == pytest.approx((10 + 2 + 4 + 12) / 2)
    # the op's rows, its grouped matmuls (not the metadata helper) and
    # both shared experts
    assert reader("device_ms_per_step.routed_ffn").compute(
        traced) == pytest.approx((4 + 2 + 16 + 8 + 4) / 2)
    flops, _ = counts.flash_mla_cost(traced["config"], traced["cell"])
    assert reader("flash_mla_roofline_share").compute(
        traced) == pytest.approx(100 * (flops / 197e12) / 0.045)


def test_a_step_without_the_new_ops_or_without_name_scopes_reads_nothing(
        traced, monkeypatch):
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows_fixture()[:1])
    assert reader("device_ms_per_step.latent_attention").compute(
        traced) == 0.0
    assert reader("device_ms_per_step.mtp").compute(traced) == 0.0
    assert reader("flash_mla_roofline_share").compute(traced) is None
    # a program whose trace join gives no `name_scope` (the parent's)

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture()]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    for name in NEW_READERS:
        if name != "flash_mla_roofline_share":
            assert reader(name).compute(traced) is None


def test_the_trace_join_gives_every_row_its_name_scope():
    """What the three `device_ms_per_step` readers stand on: the
    executor lowers an op built under `fluid.name_scope()` as
    "<path>/<op_type>:<op_index>", `join_events` still reads every op's
    type, and a program without a name scope lowers as before."""
    from paddle_tpu.observe import trace

    programs = {"jit_step": {
        "fusion.1": {"op_name": "jit(step)/jit(main)/transpose(jvp("
                     "mtp/latent_attention/mul:12))/dot_general",
                     "bucket": "matmul", "flops": 1.0, "bytes": 1.0,
                     "kernel": None},
        "fusion.2": {"op_name": "jit(step)/jit(main)/jvp(mul:3)/dot_general",
                     "bucket": "matmul", "flops": 1.0, "bytes": 1.0,
                     "kernel": None},
        "fusion.3": {"op_name": "jit(step)/jit(main)/adam:700/mul",
                     "bucket": "elementwise", "flops": 1.0, "bytes": 1.0,
                     "kernel": None}}}
    rows = trace.join_events(
        [("%fusion.1 = f32[2] fusion(...)", 0.0, 1.0, "jit_step"),
         ("%fusion.2 = f32[2] fusion(...)", 1.0, 1.0, "jit_step"),
         ("%fusion.3 = f32[2] fusion(...)", 2.0, 1.0, "jit_step")],
        [], programs)
    by = {r["instruction"]: r for r in rows}
    assert (by["fusion.1"]["name_scope"], by["fusion.1"]["op_type"],
            by["fusion.1"]["phase"]) == ("mtp/latent_attention", "mul",
                                         "backward")
    assert (by["fusion.2"]["name_scope"], by["fusion.2"]["op_type"]) == (
        "", "mul")
    assert (by["fusion.3"]["name_scope"], by["fusion.3"]["op_type"]) == (
        "", "adam")

    import paddle_tpu as fluid
    from paddle_tpu import layers

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data(name="x", shape=[4], dtype="float32")
        plain = layers.fc(x, size=3)
        with fluid.name_scope("mtp"), fluid.name_scope("shared_expert"):
            scoped = layers.fc(x, size=3)
    attrs = {o.desc.outputs["Out"][0]: o.desc.attrs
             for o in main.global_block().ops if o.type == "mul"}
    assert "__name_scope__" not in attrs[[
        n for n in attrs if not n.startswith("mtp/")][0]]
    assert [a["__name_scope__"] for n, a in attrs.items()
            if n.startswith("mtp/")] == ["mtp/shared_expert"]
    assert plain is not None and scoped is not None


def test_toy_joyai_cell_runs_the_harness(capfd):
    result = bench_run.run_cell("tiny-joyai-host", 2**31 + 11, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out
    assert set(result["metrics"]) == {"dispatch_ms.train",
                                      "compiles_in_window"}
    out = capfd.readouterr().out
    assert '"loss_fell": true' in out


def test_parity_script_compares_both_heads_where_the_experts_agree():
    parity = load("joyai_parity")
    n, last, layers = 300, parity.LAST, 5
    experts = np.tile(np.arange(8), (layers, n, 1))
    want = {"logits": np.zeros((last, 5), np.float32),
            "mtp_logits": np.zeros((last, 5), np.float32),
            "ce": 2.0, "mtp_ce": 3.0, "experts": experts,
            "counts": np.full((layers, 8), 37),
            "grad_names": ["embed", "layer1.router", "head"],
            "grads": [np.ones((3, 2), np.float32),
                      np.zeros((2, 2), np.float32),
                      np.full((4,), 2.0, np.float32)]}
    got = dict(want, logits=want["logits"].copy(),
               mtp_logits=want["mtp_logits"].copy(), mtp_ce=3.002,
               experts=experts.copy(),
               grads=[np.ones((3, 2), np.float32),
                      np.zeros((2, 2), np.float32),
                      np.full((4,), 2.2, np.float32)])
    got["logits"][-1, 0] = 0.5            # a token routed elsewhere,
    got["experts"][4, -1, 0] = 255        # in the module's layer
    got["mtp_logits"][3, 1] = 0.02        # the module's head is compared
    got["logits"][3, 1] = 0.01
    c = parity.compare(got, want)
    assert c["logit_err_max"] == pytest.approx(0.02)
    assert c["logit_err_all_max"] == pytest.approx(0.5)
    assert c["flipped_share"] == pytest.approx(1 / (layers * n))
    assert c["flipped_in_tail"] == 1 and c["counts_equal"]
    assert c["loss_err"] == pytest.approx(0.002)
    assert c["grad_err_worst"] == pytest.approx(0.1, rel=1e-5)
    assert c["grad_err_worst_leaf"] == "head"
    assert c["grad_dead_leaves"] == ["layer1.router"]
    got["grads"][1] = np.full((2, 2), 1e-9, np.float32)
    assert parity.compare(got, want)["grad_err_worst"] == float("inf")
    # and end to end at a toy size on the CPU: float32 inside its limits
    _, config, family = bench_run.load_cell("tiny-joyai-host",
                                            (BENCH, FIXTURES))
    parity.LAST, parity.Q_BLOCK, parity.GRAD_Q_BLOCK = 16, 8, 8
    r = parity.check_seed(config, family, 2**31 + 3)
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_loss"]
    assert checks["f32_routing"] and checks["f32_held_counts"]
    assert checks["share_is_a_share"]
    assert checks["grads_are_compared"] and checks["f32_grads"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-4
    assert len(r["f32"]["held_rows"]) == 2      # the layer's, the module's
