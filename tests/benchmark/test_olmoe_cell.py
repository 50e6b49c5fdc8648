"""The `olmoe-1b-7b` configuration, its family, the `olmoe-4k` cell and
the readers PR 26 adds, on the CPU: the files and BENCHMARK.json agree,
the configuration holds the catalog's numbers, `train_flops` against a
hand count, each reader on a fixture and without a trace, the two
copies of the plain reference, and the parity script's arithmetic.
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
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
CATALOG = {      # the catalog row's `config`, OLMoE-1B-7B-0125-Instruct
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "tie_word_embeddings": False, "vocab_size": 50304}
NEW_READERS = {
    "device_ms_per_step.moe": ["olmoe-4k"],
    "device_ms_per_step.custom_call": None,
    "flash_roofline_share": ["olmoe-4k"],
    "expert_matmul_roofline_share": ["olmoe-4k"],
    "moe_expert_load_max_over_mean": ["olmoe-4k"]}


def real():
    return bench_run.load_cell("olmoe-4k", (BENCH,))


def reader(name):
    return bench_run.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_configuration_holds_the_published_numbers_and_one_cut():
    _, config, _ = real()
    differs = {k for k, v in CATALOG.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 1
    assert "16 -> 1" in config["reduced_why"]
    bj = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = [c for c in bj["configs"] if c["name"] == "olmoe-1b-7b"][0]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == ["num_hidden_layers"]
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["aux_loss_weight"], t["z_loss_weight"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.01, 0.001)
    assert {"expert width", "training", "weights", "batch",
            "deployment"} <= set(config["assumed"])


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == ("olmoe-1b-7b", "b4-len4096-host", 1, None,
                              4, 4096, "host", 8)
    bj = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    # looked up by name: every later PR appends to both lists
    assert "olmoe-4k" in tokens["workloads"]
    entry = [w for w in bj["workloads"] if w["name"] == "olmoe-4k"]
    assert len(entry) == 1
    assert (entry[0]["config"], entry[0]["traffic"], entry[0]["chips"]) == (
        cell["config"], cell["traffic"], cell["chips"])
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": 16384, "unit": "tokens/s"}}


def test_olmoe_train_flops_by_hand():
    cell, config, family = real()
    d, dff, t, vocab = 2048, 1024, 4096, 50304
    proj = 4 * 2 * d * d                    # q, k, v, o
    attn = 2 * 2 * t * d // 2               # scores + values, causal
    router = 2 * d * 64
    experts = 8 * 3 * 2 * d * dff           # 8 ACTIVE experts, 3 matmuls
    head = 2 * d * vocab
    assert (proj, attn, router, experts, head) == (
        33554432, 16777216, 262144, 100663296, 206045184)
    assert family.forward_flops_per_token(config, t) == {
        "projections": proj, "attention": attn, "router": router,
        "experts": experts, "head": head}
    per_token = proj + attn + router + experts + head
    assert per_token == 357302272           # forward, one token
    step = 3 * per_token * 4 * 4096
    assert step == 17562121273344           # 17.56 TFLOP a step
    assert family.train_flops(config, cell) == pytest.approx(step,
                                                             rel=1e-12)
    assert head / per_token == pytest.approx(0.5767, abs=1e-4)
    # the 16-layer model: the head is 8% of it
    full = dict(config, num_hidden_layers=16)
    parts = family.forward_flops_per_token(full, t)
    assert parts["head"] / sum(parts.values()) == pytest.approx(0.0785,
                                                                abs=1e-3)


def test_make_batch_is_shifted_by_one_and_seeded():
    cell, config, family = real()
    small = dict(cell, batch_per_chip=2)
    a = family.make_batch(config, small, np.random.default_rng(2**31 + 5))
    b = family.make_batch(config, small, np.random.default_rng(2**31 + 5))
    assert a["tokens"].shape == a["labels"].shape == (2, 4096)
    assert a["tokens"].dtype == np.int64
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].min() >= 1 and a["tokens"].max() < 50304
    # Zipf-like: the ten commonest ids carry a real share
    assert (a["tokens"] <= 10).mean() > 0.04
    with pytest.raises(ValueError, match="not the context"):
        family.make_batch(config, dict(cell, length=256),
                          np.random.default_rng(0))


def test_new_readers_match_benchmark_json_and_read_none_without_a_trace():
    bj = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bj["per_layer"]}
    assert set(NEW_READERS) <= set(listed)
    cell, config, _ = real()
    no_trace = {"cell": cell, "config": config, "trace": None, "steps": 5}
    for name, cells in NEW_READERS.items():
        module = reader(name)
        assert module.META["cells"] == cells == listed[name].get(
            "workloads")
        assert module.META["moves"] == "mfu"
        if module.META["source"] == "device_trace":
            assert module.compute(no_trace) is None
    # no routed layer has run in this process's scopes yet
    from paddle_tpu.core.executor import Scope

    if not any(n.endswith(".token_count") for s in Scope.live
               for n in s.local_var_names()):
        assert reader("moe_expert_load_max_over_mean").compute(
            no_trace) is None


def rows_fixture():
    """Rows as `observe/trace.op_rows` gives them for 2 traced steps."""
    def row(instruction, bucket, self_s, op_type=None, op_name="",
            kernel=None, with_kernel_key=True):
        r = {"module": "jit_step(1)", "instruction": instruction,
             "bucket": bucket, "self_s": self_s, "calls": 2,
             "op_type": op_type, "op_name": op_name, "phase": "forward",
             "flops": 0.0}
        if with_kernel_key:
            r["kernel"] = kernel
        return r

    return [
        row("fusion.1", "matmul", 0.010, "mul"),
        row("fusion.2", "elementwise", 0.004, "moe_dropless"),
        row("sort.1", "elementwise", 0.002, "moe_dropless"),
        row("ragged-dot-none.1", "custom_call", 0.040,
            op_name="ragged-dot-none", kernel="ragged_dot"),
        row("ragged-dot-metadata", "custom_call", 0.001,
            op_name="ragged-dot-metadata", kernel="ragged_dot_metadata"),
        row("custom-call.3", "custom_call", 0.006, "flash_attention",
            "jit(step)/flash_attention:9/pallas_flash_fwd",
            kernel="flash_fwd"),
        # a program from before rows named their kernel: the scope does
        row("custom-call.4", "custom_call", 0.014, "flash_attention",
            "jit(step)/transpose(jvp(flash_attention:9))/pallas_flash_dkv",
            with_kernel_key=False),
        row("custom-call.9", "custom_call", 0.003,
            op_name="ConcatBitcast"),          # the compiler's own
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
    # Mosaic kernels only: 40 + 6 + 14 ms over 2 steps, not the
    # metadata helper, not ConcatBitcast
    assert reader("device_ms_per_step.custom_call").compute(
        traced) == pytest.approx(30.0)
    # the op's own rows (4 + 2) and its grouped matmuls (40), a step
    assert reader("device_ms_per_step.moe").compute(
        traced) == pytest.approx(23.0)
    flops, nbytes = kernel_counts.flash_attention_cost(
        traced["config"], traced["cell"])
    assert flops == 7 * 4 * 16 * 4096 * 4096 * 128 == 962072674304
    assert nbytes == 12 * 4 * 4096 * 2048 * 2
    # compute-bound: 4.884 ms at peak over 10 ms measured
    assert reader("flash_roofline_share").compute(
        traced) == pytest.approx(100 * (flops / 197e12) / 0.010)
    flops, nbytes = kernel_counts.expert_matmul_cost(
        traced["config"], traced["cell"])
    assert flops == 9 * 2 * 131072 * 2048 * 1024 == 4947802324992
    assert reader("expert_matmul_roofline_share").compute(
        traced) == pytest.approx(100 * (flops / 197e12) / 0.020)
    assert 0 < reader("flash_roofline_share").compute(traced) < 100


def test_a_step_without_kernels_reads_zero_not_none(traced, monkeypatch):
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows_fixture()[:1])
    assert reader("device_ms_per_step.custom_call").compute(traced) == 0.0
    assert reader("flash_roofline_share").compute(traced) is None
    assert reader("expert_matmul_roofline_share").compute(traced) is None


def test_toy_olmoe_cell_runs_the_harness_and_counts_on_the_device(capfd):
    result = bench_run.run_cell("tiny-olmoe-host", 2**31 + 9, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left
    # out; the counters report, the routing ratio among them (through
    # fixtures/layer_metrics/tiny_moe_load.py, read while the cell's
    # scope is alive: 8 experts, so between 1 and 8)
    assert set(result["metrics"]) == {"dispatch_ms.train",
                                      "compiles_in_window",
                                      "tiny_moe_load"}
    assert 1.0 <= result["metrics"]["tiny_moe_load"]["value"] <= 8.0


def test_both_copies_of_the_reference_give_the_same_numbers():
    import jax.numpy as jnp

    from paddle_tpu.models import decoder_reference as package_copy

    spec = importlib.util.spec_from_file_location(
        "reference_olmoe", os.path.join(BENCH, "reference_olmoe.py"))
    bench_copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_copy)
    cfg = {"hidden_size": 32, "num_attention_heads": 2,
           "rms_norm_eps": 1e-5, "rope_theta": 10000, "num_experts": 4,
           "num_experts_per_tok": 2, "norm_topk_prob": False,
           "tie_word_embeddings": False}
    rng = np.random.default_rng(0)
    shapes = [(50, 32), (32,), (32, 32), (32,), (32, 32), (32,), (32, 32),
              (32, 32), (32,), (32, 4), (4, 32, 16), (4, 16, 32),
              (4, 32, 16), (32,), (32, 50)]
    arrays = [rng.normal(size=s).astype(np.float32) * 0.2 for s in shapes]
    ids = rng.integers(0, 50, size=(2, 9))
    out = []
    for module in (package_copy, bench_copy):
        (total, parts), grads = module.loss_and_grads(
            module.params_from_list(arrays, 1), jnp.asarray(ids[:, :-1]),
            jnp.asarray(ids[:, 1:]), cfg)
        out.append((float(total), np.asarray(parts["logits"]),
                    np.asarray(grads["layers"][0]["w2"])))
    assert out[0][0] == out[1][0]
    np.testing.assert_array_equal(out[0][1], out[1][1])
    np.testing.assert_array_equal(out[0][2], out[1][2])


def test_parity_script_compares_on_the_tokens_whose_experts_agree():
    spec = importlib.util.spec_from_file_location(
        "olmoe_parity", os.path.join(BENCH, "olmoe_parity.py"))
    parity = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(parity)
    n, last = 300, parity.LAST
    experts = np.tile(np.arange(8), (n, 1))
    want = {"logits": np.zeros((last, 5), np.float32), "loss": 2.0,
            "aux": 1.0, "z": 3.0, "experts": experts,
            "counts": np.full(8, n)}
    got = dict(want, logits=want["logits"].copy(), loss=2.001,
               experts=experts.copy())
    got["logits"][-1, 0] = 0.5            # a token routed elsewhere
    got["experts"][-1, 0] = 63
    got["logits"][3, 1] = 0.01
    c = parity.compare(got, want)
    assert c["logit_err_max"] == pytest.approx(0.01)
    assert c["logit_err_all_max"] == pytest.approx(0.5)
    assert c["flipped_share"] == pytest.approx(1 / n)
    assert c["flipped_in_tail"] == 1 and c["counts_equal"]
    assert c["loss_err"] == pytest.approx(0.001)
    # and end to end at a toy size on the CPU: float32 inside its limits
    _, config, family = bench_run.load_cell("tiny-olmoe-host",
                                            (BENCH, FIXTURES))
    parity.LAST = 16
    r = parity.check_seed(config, family, 2**31 + 3)
    assert r["checks"]["f32_logits"] and r["checks"]["f32_loss"]
    assert r["checks"]["f32_routing"] and r["checks"]["dropless"]
    assert r["f32"]["counts_equal"] and r["f32"]["flipped_share"] == 0.0
