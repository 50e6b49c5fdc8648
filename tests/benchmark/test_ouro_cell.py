"""The `ouro-2.6b` configuration, its family, the `ouro-4k` cell and
the readers PR 36 adds, on the CPU: the files and BENCHMARK.json agree
(entries looked up BY NAME: the next cell is appended after this one),
the configuration holds the catalog's numbers and exactly one cut, the
family's map onto the builder, `train_flops` against hand counts, each
reader on a fixture and without a trace, the parity script's arithmetic
at a toy size, and a toy cell through `run_cell`.  No number from here
is a speed.
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

import loop_rows  # noqa: E402
import run as bench_run  # noqa: E402
import step_anatomy  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
CATALOG = {      # the catalog row's `config`, Ouro-2.6B
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
NEW_READERS = ["device_ms_per_step.ut_loop", "device_ms_per_step.exit_head",
               "loop_body_joined_share"]
T, D, DFF, V, LAYERS, TRIPS = 4096, 2048, 5632, 49152, 8, 4


def real():
    return bench_run.load_cell("ouro-4k", (BENCH,))


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


def test_configuration_holds_the_published_numbers_and_exactly_one_cut():
    _, config, _ = real()
    differs = [k for k, v in CATALOG.items() if config.get(k, "absent") != v]
    # the layer pattern is shortened with the depth, and only with it
    assert sorted(differs) == ["layer_types", "num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == LAYERS >= 4       # the floor
    assert config["layer_types"] == ["full_attention"] * LAYERS
    assert config["published"] == {"num_hidden_layers": 48}
    # the mechanism and the whole vocabulary are never cut
    assert (config["total_ut_steps"], config["vocab_size"],
            config["num_attention_heads"], config["sequence_length"]) == (
        TRIPS, V, 16, T)
    assert "48 -> 8" in config["reduced_why"]
    entry = [c for c in benchmark_json()["configs"]
             if c["name"] == "ouro-2.6b"][0]
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmarks/configs/ouro-2.6b.json"
    t = config["training"]
    assert (t["learning_rate"], t["beta1"], t["beta2"], t["epsilon"],
            t["weight_decay"], t["warmup_steps"], t["clip_norm"],
            t["exit_entropy_weight"], t["recompute"], t["use_amp"]) == (
        4e-4, 0.9, 0.95, 1e-8, 0.1, 2000, 1.0, 0.1, "layer", True)
    assert {"sandwich_norm", "qk_norm", "biases", "exit_gate",
            "exit_entropy_weight", "early_exit_threshold", "weights",
            "training", "sequence_length", "recomputation"} \
        <= set(config["assumed"])
    assert "not read by the training path" \
        in config["assumed"]["early_exit_threshold"]


def test_the_family_maps_the_published_keys_onto_the_builder():
    """The keys go through unrenamed, the three equations no key spells
    are arguments named for the mechanism, and a value the builder does
    not build raises."""
    _, config, family = real()
    args = family.architecture(config)
    assert (args["sandwich_norm"], args["qk_norm"], args["exit_gate"]) == (
        True, None, "sigmoid")
    assert (args["num_experts"], args["num_dense_layers"],
            args["total_ut_steps"]) == (0, LAYERS, TRIPS)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "rms_norm_eps", "rope_theta",
                "vocab_size", "tie_word_embeddings", "total_ut_steps"):
        assert args[key] == CATALOG[key], key
    assert not {"model_type", "head_dim", "early_exit_threshold",
                "max_window_layers", "hidden_act"} & set(args)
    import inspect

    from paddle_tpu.models import decoder

    assert set(args) <= set(inspect.signature(decoder.decoder).parameters)
    assert set(config["training"]) <= (
        set(inspect.signature(decoder.build_model).parameters)
        | set(inspect.signature(decoder.decoder).parameters))
    for key, value in (("hidden_act", "gelu"), ("use_sliding_window", True),
                       ("sliding_window", 4096),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(NotImplementedError, match=key):
            family.architecture(dict(config, **{key: value}))
    with pytest.raises(ValueError, match="head_dim"):
        family.architecture(dict(config, head_dim=64))
    # no model's name in the program
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    assert "ouro" not in f.read().lower(), name


def test_parameters_by_hand():
    """612.5 M parameters: 7.35 GB of float32 master weights and two
    Adam moments, 9.8 GB with a float32 gradient beside them."""
    layer = 4 * D * D + 3 * D * DFF + 4 * D
    assert 4 * D * D + 3 * D * DFF == 51380224            # "51.4 M"
    total = 2 * V * D + LAYERS * layer + D + D + 1
    assert total == 612438017
    assert round(12 * total / 1e9, 2) == 7.35
    assert round(16 * total / 1e9, 1) == 9.8
    # the published depth: the "2.6B"
    assert round((2 * V * D + 48 * layer) / 1e9, 2) == 2.67


def test_cell_is_the_issues_and_joins_tokens_per_s():
    cell, config, family = real()
    assert (cell["config"], cell["traffic"], cell["chips"], cell["mesh"],
            cell["batch_per_chip"], cell["length"], cell["feed"],
            cell["pool"]) == (
        "ouro-2.6b", "b1-len4096-host", 1, None, 1, T, "host", 4)
    assert len(cell["why"]) <= 200 and "3.4%" in cell["why"]
    bj = benchmark_json()
    tokens = [m for m in bj["end_to_end"] if m["name"] == "tokens_per_s"][0]
    assert "ouro-4k" in tokens["workloads"]
    assert [w for w in bj["workloads"] if w["name"] == "ouro-4k"] == [{
        "name": "ouro-4k", "config": "ouro-2.6b",
        "traffic": "b1-len4096-host", "chips": 1, "why": cell["why"]}]
    assert len(bj["workloads"]) >= 7
    assert sum(w["chips"] == 4 for w in bj["workloads"]) == 1
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": T, "unit": "tokens/s"}}


def test_ouro_train_flops_by_hand():
    cell, config, family = real()
    passes = TRIPS * LAYERS
    want = {"projections": passes * 4 * 2 * D * D,
            "attention": passes * 2 * 2 * T * D / 2,
            "ffn": passes * 3 * 2 * D * DFF,
            "head": TRIPS * 2 * D * V,
            "gate": TRIPS * 2 * D}
    got = family.forward_flops_per_token(config, T)
    assert got == pytest.approx(want)
    # a layer pass is 119.6 MFLOP a token
    assert (want["projections"] + want["attention"] + want["ffn"]) \
        / passes == pytest.approx(119.6e6, rel=1e-3)
    total = sum(got.values())
    assert family.train_flops(config, cell) == pytest.approx(
        3 * total * T) == pytest.approx(56.9e12, rel=1e-3)
    share = {k: 100 * v / total for k, v in got.items()}
    # the loop's body is 82.6% of the model FLOPs, the four heads 17.4
    assert round(share["ffn"], 1) == 47.8
    assert round(share["projections"], 1) == 23.2
    assert round(share["attention"], 1) == 11.6
    assert round(share["head"], 1) == 17.4
    # ... and 3.4% at the published depth
    full = dict(config, num_hidden_layers=48)
    f = family.forward_flops_per_token(full, T)
    assert round(100 * f["head"] / sum(f.values()), 1) == 3.4


def test_make_batch_draws_shifted_views_over_the_whole_vocabulary():
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
    for name in NEW_READERS:
        module = reader(name)
        assert module.META["cells"] == ["ouro-4k"] == listed[name][
            "workloads"]
        assert module.META["moves"] == "mfu" == listed[name]["moves"]
        assert module.META["unit"] == listed[name]["unit"]
        assert module.META["layer"] == listed[name]["layer"]
        assert module.META["source"] == "device_trace" == listed[name][
            "source"]
        assert module.compute(no_trace) is None
    # every all-cell reader is the cell's too, and no other cell's is
    readers = bench_run.layer_readers("ouro-4k", (BENCH,))
    everywhere = {m["name"] for m in benchmark_json()["per_layer"]
                  if "workloads" not in m}
    assert everywhere | set(NEW_READERS) <= set(readers)
    # a later PR may add a reader for this cell: it names the cell
    for name in set(readers) - everywhere - set(NEW_READERS):
        assert "ouro-4k" in readers[name].META["cells"]
    assert not set(NEW_READERS) & set(
        bench_run.layer_readers("joyai-8k", (BENCH,)))


BODY = ("jit(step)/transpose(jvp(ut_loop/static_rnn:9))/while/body/"
        "closed_call/checkpoint/")


def rows_fixture():
    """Rows as `observe/trace.op_rows` gives them for 2 traced steps."""
    def row(instruction, bucket, self_s, scope="", op_type=None,
            kernel=None, flops=0.0, op_name="", joined=True, calls=8):
        return {"module": "jit_step(1)", "instruction": instruction,
                "bucket": bucket, "self_s": self_s, "calls": calls,
                "op_type": op_type, "name_scope": scope, "op_name": op_name,
                "phase": "backward", "flops": flops, "kernel": kernel,
                "joined": joined}

    inner = "while/body/closed_call/checkpoint/ut_loop"
    return [
        row("while.1", "loop", 0.004, "ut_loop", "static_rnn", calls=2),
        row("fusion.1", "matmul", 0.200, inner, "mul", flops=3e9),
        row("fusion.2", "elementwise", 0.040, inner, "rms_norm"),
        row("custom-call.1", "custom_call", 0.100, inner,
            "flash_attention", kernel="flash_fwd",
            op_name=BODY + "ut_loop/flash_attention:7/pallas_flash_fwd"),
        row("fusion.3", "matmul", 0.060, inner + "/exit_head", "mul",
            flops=2e9),
        row("fusion.4", "elementwise", 0.020, inner + "/exit_head",
            "softmax_with_cross_entropy"),
        # dark rows: a body instruction without a cost row, a matmul
        # without FLOPs, a Mosaic call without its name, an event in
        # no map
        row("fusion.5", "loop", 0.010, inner, "mul"),
        row("fusion.6", "matmul", 0.006, inner, "mul", flops=None),
        row("custom-call.2", "custom_call", 0.008, inner,
            "flash_attention",
            op_name=BODY + "ut_loop/flash_attention:7/pallas_flash_dq"),
        row("fusion.7", "unknown", 0.002, inner, joined=False),
        # the TPU compiler's own custom call has no kernel name to lose
        row("custom-call.3", "custom_call", 0.001, inner, "mul",
            op_name=BODY + "ut_loop/mul:3/dot_general"),
        # outside the loop: the embedding, the exit loss, the optimizer
        row("fusion.8", "elementwise", 0.050, "", "adam", calls=2),
        row("fusion.9", "elementwise", 0.003, "exit_loss", "exp", calls=2),
    ]


@pytest.fixture
def traced(monkeypatch):
    cell, config, _ = real()
    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows_fixture())
    return {"cell": cell, "config": config, "steps": 2,
            "trace": {"path": "x", "chip0": {"lo": 0.0, "hi": 1.0,
                                             "steps": 2}}}


def test_readers_on_a_fixture(traced):
    # the loop without its heads: the `while`'s own time, the layer
    # passes, the dark rows too
    assert reader("device_ms_per_step.ut_loop").compute(
        traced) == pytest.approx(
        (4 + 200 + 40 + 100 + 10 + 6 + 8 + 2 + 1) / 2)
    assert reader("device_ms_per_step.exit_head").compute(
        traced) == pytest.approx((60 + 20) / 2)
    lit = 200 + 40 + 100 + 60 + 20 + 1
    dark = 4 + 10 + 6 + 8 + 2
    assert reader("loop_body_joined_share").compute(
        traced) == pytest.approx(100 * lit / (lit + dark))
    assert [loop_rows.lit(r) for r in rows_fixture()[:2]] == [False, True]


def test_a_loop_gone_dark_or_a_step_without_name_scopes(traced,
                                                        monkeypatch):
    """The reader's reason: were the body's instructions to lose their
    cost rows, the loop's time falls into `loop` and the share to
    nothing; a program whose trace join gives no `name_scope` (the
    parent's) reads nothing at all."""
    def dark(path, lo, hi):
        return [dict(r, bucket="loop", flops=None, kernel=None)
                if "ut_loop" in r["name_scope"] else r
                for r in rows_fixture()]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", dark)
    assert reader("loop_body_joined_share").compute(traced) == 0.0
    assert reader("device_ms_per_step.ut_loop").compute(traced) > 0

    monkeypatch.setattr(step_anatomy, "_chip0_rows",
                        lambda path, lo, hi: rows_fixture()[-2:])
    assert reader("device_ms_per_step.ut_loop").compute(traced) == 0.0
    assert reader("loop_body_joined_share").compute(traced) is None

    def parents(path, lo, hi):
        return [{k: v for k, v in r.items() if k != "name_scope"}
                for r in rows_fixture()]

    monkeypatch.setattr(step_anatomy, "_chip0_rows", parents)
    for name in NEW_READERS:
        assert reader(name).compute(traced) is None


def test_toy_ouro_cell_runs_the_harness(capfd):
    from paddle_tpu.observe.monitoring import runtime_stats

    before = runtime_stats.snapshot()
    result = bench_run.run_cell("tiny-ouro-host", 2**31 + 11, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    assert result["correct"] is True and result["failed"] == 0
    # a CPU trace holds no device plane: the device readers are left out
    assert set(result["metrics"]) == {"dispatch_ms.train",
                                      "compiles_in_window"}
    out = capfd.readouterr().out
    assert '"loss_fell": true' in out
    # the counter reads the Program's own trip count, once a step build
    assert runtime_stats.delta(before)["loop_trips"] == 4


def test_parity_script_compares_every_trip_and_every_leaf():
    parity = load("ouro_parity")
    last = parity.LAST
    want = {"logits": np.zeros((4, last, 5), np.float32),
            "p": np.full((4, 7), 0.25, np.float32),
            "loss": 2.0, "ut_ce": np.full((4,), 3.0),
            "grad_names": ["embed", "wq", "head"],
            "grads": [np.ones((3, 2), np.float32),
                      np.full((2, 2), 3.0, np.float32),
                      np.full((4,), 2.0, np.float32)]}
    got = dict(want, logits=want["logits"].copy(), p=want["p"].copy(),
               loss=2.002, ut_ce=np.array([3.0, 3.0, 3.001, 3.0]),
               grads=[np.ones((3, 2), np.float32),
                      np.full((2, 2), 3.0, np.float32),
                      np.full((4,), 2.2, np.float32)])
    got["logits"][2, 3, 1] = 0.02         # the third trip's head
    got["p"][1, 4] = 0.26
    c = parity.compare(got, want)
    assert c["logit_err_by_trip"] == pytest.approx([0, 0, 0.02, 0])
    assert c["logit_err_max"] == pytest.approx(0.02)
    assert c["p_err_max"] == pytest.approx(0.01)
    assert c["loss_err"] == pytest.approx(0.002)
    assert c["ut_ce_err"] == pytest.approx(0.001)
    assert c["grad_err_worst"] == pytest.approx(0.1, rel=1e-5)
    assert c["grad_err_worst_leaf"] == "head"
    assert c["grad_dead_leaves"] == []
    # a trip's part of a shared leaf dropped: a quarter of it missing
    got["grads"][1] = np.full((2, 2), 2.25, np.float32)
    c = parity.compare(got, want)
    assert c["grad_err_worst_leaf"] == "wq"
    assert c["grad_err_worst"] == pytest.approx(0.25)
    # and end to end at a toy size on the CPU: float32 inside its limits
    _, config, family = bench_run.load_cell("tiny-ouro-host",
                                            (BENCH, FIXTURES))
    parity.LAST, parity.Q_BLOCK = 16, 8
    r = parity.check_seed(config, family, 2**31 + 3)
    checks = r["checks"]
    assert checks["f32_logits"] and checks["f32_exit_p"]
    assert checks["f32_loss"] and checks["every_trip_weighs"]
    assert checks["grads_are_compared"] and checks["f32_grads"]
    assert 0.0 < r["f32"]["grad_err_worst"] < 1e-4
    assert len(r["f32"]["logit_err_by_trip"]) == 4
