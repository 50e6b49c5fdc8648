"""The benchmark harness (benchmarks/, BENCHMARK.json) on the CPU.

What a chip run cannot be asked to prove again every time: the interval
arithmetic of the trace reduction on hand-made events, the FLOP counts
against numbers worked out by hand, the loop's bookkeeping at a toy
size, that the command refuses a stand-in device, and that a cell, a
configuration and a per-layer metric are added as new files only.
No number from here is a speed: the toy cell runs on the CPU.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
FIXTURES = os.path.join(HERE, "fixtures")
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
import trace_reduce as tr  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "bf16_flops": 1e12}


def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# --------------------------------------------------------------------------
# trace_reduce: interval arithmetic on hand-made event lists
# --------------------------------------------------------------------------

# (name, start_s, duration_s): a overlaps b, c is nested in b, d is apart
EVENTS = [("a", 1.0, 2.0), ("b", 2.5, 2.5), ("c", 3.0, 0.5),
          ("d", 7.0, 1.0)]


def test_busy_union_merges_overlapping_and_nested_events():
    assert tr.busy_union(EVENTS) == [(1.0, 5.0), (7.0, 8.0)]
    assert tr.busy_union([]) == []
    # touching intervals are one
    assert tr.busy_union([("x", 0.0, 1.0), ("y", 1.0, 1.0)]) == [(0.0, 2.0)]


def test_busy_seconds_and_idle_share_inside_a_window():
    busy = tr.busy_union(EVENTS)
    # window [2, 9]: busy 2..5 and 7..8 = 4 s of 7 s
    assert tr.busy_seconds(busy, 2.0, 9.0) == pytest.approx(4.0)
    assert 1 - tr.busy_seconds(busy, 2.0, 9.0) / 7.0 == pytest.approx(3 / 7)
    # window [0, 10]: 5 s of 10 s
    assert tr.busy_seconds(busy, 0.0, 10.0) == pytest.approx(5.0)
    assert tr.idle_gaps(busy, 0.0, 10.0) == [(0.0, 1.0), (5.0, 7.0),
                                             (8.0, 10.0)]
    assert tr.idle_gaps(busy, 2.0, 7.5) == [(5.0, 7.0)]
    assert tr.idle_gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_gap_is_named_after_the_span_that_covers_its_start():
    busy = tr.busy_union(EVENTS)
    spans = [("bench.executor_run", 4.0, 0.5),
             ("bench.lagged_sync", 4.6, 3.0),      # covers 5.0
             ("bench.make_batch", 7.9, 0.05)]
    gaps = tr.longest_gaps(busy, 0.5, 9.0, spans, top=2)
    assert gaps[0][0] == "bench.lagged_sync"       # 5..7, the longest
    assert gaps[0][1] == pytest.approx(2.0)
    assert gaps[1] == ["none", pytest.approx(1.0)]  # 8..9: no span at 8.0
    # nested spans: the innermost names the gap
    nested = [("bench.outer", 0.0, 10.0), ("bench.inner", 4.9, 0.2)]
    assert tr.covering_span(5.0, nested) == "bench.inner"
    assert tr.covering_span(6.0, nested) == "bench.outer"
    assert tr.covering_span(11.0, nested) == "none"


def test_op_table_shares_sum_to_one_and_names_are_cut_short():
    hlo = ("%fusion.7 = bf16[64,256]{1,0:T(8,128)(2,1)} "
           "fusion(f32[8]{0} %p), kind=kLoop")
    events = [(hlo, 0.0, 3.0), (hlo, 5.0, 1.0), ("%copy.1 = f32[4]{0} "
              "copy(f32[4]{0} %x)", 4.0, 4.0), ("late", 20.0, 9.0)]
    table = tr.op_table(events, 0.0, 10.0)
    assert [r[0] for r in table] == ["fusion.7 bf16[64,256]",
                                     "copy.1 f32[4]"]
    assert [r[1] for r in table] == [4.0, 4.0]
    assert sum(r[2] for r in table) == pytest.approx(1.0)
    # cut to the top row, shares still of the whole
    assert tr.op_table(events, 0.0, 10.0, top=1)[0][2] == pytest.approx(0.5)


def test_collectives_are_found_by_opcode_in_both_sync_and_async_form():
    events = [("%all-reduce.3 = f32[8]{0} all-reduce(...)", 0.0, 2.0),
              ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(...)",
               3.0, 0.5),
              ("%all-reduce-done.1 = f32[8]{0} all-reduce-done(...)",
               4.0, 0.25),
              ("%all-gather.2 = f32[8]{0} all-gather(...)", 5.0, 1.0),
              ("%fusion.9 = f32[8]{0} fusion(%all-reduce.3)", 6.0, 7.0),
              ("%reduce-scatter.1 = f32[2]{0} reduce-scatter(...)",
               50.0, 1.0)]
    assert tr.collective_seconds(events, 0.0, 10.0) == pytest.approx(3.75)


def test_step_window_is_cut_to_whole_steps_of_the_biggest_module():
    modules = ([("jit_step(1)", 0.1 * i, 0.09) for i in range(10)]
               + [("jit_convert(2)", 0.05, 0.001)])
    lo, hi, steps = tr.step_window(modules, skip=2)
    assert (lo, hi, steps) == (pytest.approx(0.2), pytest.approx(0.9), 7)
    assert tr.step_window(modules[:3], skip=2) is None
    assert tr.step_window([]) is None


# --------------------------------------------------------------------------
# train_flops of both families against hand-worked numbers
# --------------------------------------------------------------------------

def test_transformer_train_flops_by_hand():
    cell, config, family = bench_run.load_cell("tbase-256", (BENCH,))
    d, dff, t, vocab = 512, 2048, 256, 32000
    proj = 2 * d * d                          # 524,288 per token
    ffn = 4 * d * dff                         # 4,194,304 per token
    assert (proj, ffn) == (524288, 4194304)
    full_attn = 2 * 2 * t * d                 # scores + values per token
    enc = 4 * proj + ffn + full_attn          # 6,815,744
    dec = (4 * proj + full_attn // 2          # self, causal at half
           + 4 * proj + full_attn             # cross
           + ffn)                             # 9,175,040
    assert (enc, dec) == (6815744, 9175040)
    per_pair = 6 * (enc + dec) + 2 * d * vocab
    assert per_pair == 128712704              # forward, one token pair
    step = 3 * per_pair * 64 * 256
    assert step == 6326486827008              # 6.33 TFLOP a step
    assert family.train_flops(config, cell) == pytest.approx(step, rel=1e-12)
    assert family.units(config, cell) == {
        "tokens_per_s": {"per_step": 16384, "unit": "tokens/s"}}
    dp4, _, _ = bench_run.load_cell("tbase-256-dp4", (BENCH,))
    assert family.train_flops(config, dp4) == pytest.approx(4 * step)
    assert family.units(config, dp4)["tokens_per_s"]["per_step"] == 65536


def test_resnet50_train_flops_by_hand():
    cell, config, family = bench_run.load_cell("resnet50-b128", (BENCH,))
    stem = 112 * 112 * 64 * 3 * 49                       # 118,013,952

    def stage(size, cin, ch, blocks):
        out = ch * 4
        first = size * size * (cin * out          # projection shortcut
                               + cin * ch + 9 * ch * ch + ch * out)
        rest = size * size * (out * ch + 9 * ch * ch + ch * out)
        return first + (blocks - 1) * rest

    stages = [stage(56, 64, 64, 3), stage(28, 256, 128, 4),
              stage(14, 512, 256, 6), stage(7, 1024, 512, 3)]
    # stage 1 by hand: 56^2 = 3136 positions; first block 64*256 (shortcut)
    # + 64*64 + 9*64*64 + 64*256 = 73,728 MAC each -> 231,211,008; the
    # other two 256*64 + 36,864 + 16,384 = 69,632 each -> 218,365,952
    assert stages[0] == 231211008 + 2 * 218365952 == 667942912
    assert stages == [667942912, 950534144, 1387266048, 732168192]
    macs = stem + sum(stages) + 2048 * 1000
    assert macs == 3857973248                            # 3.86 GMAC
    assert family.forward_macs(config) == macs
    assert family.train_flops(config, cell) == pytest.approx(
        3 * 2 * macs * 128)                              # 2.96 TFLOP
    assert family.units(config, cell) == {
        "images_per_s": {"per_step": 128, "unit": "images/s"}}


# --------------------------------------------------------------------------
# the loop, in-process, on a test-only toy cell
# --------------------------------------------------------------------------

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def check_line(result, metric_names):
    line = json.loads(json.dumps(result))      # it must print as JSON
    assert RESULT_KEYS <= set(line)
    assert set(line["metrics"]) == set(metric_names)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float) and " " not in m["unit"]
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    assert line["correct"] is True and line["failed"] == 0


def test_toy_cell_end_to_end_line_and_lagged_sync_count(capfd):
    result = bench_run.run_cell("tiny-host", 2**31 + 77, 1.0, False,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    check_line(result, ["mfu", "tokens_per_s", "step_ms_p95", "setup_s"])
    detail = [json.loads(l) for l in capfd.readouterr().out.splitlines()
              if l.startswith('{"workload"')][0]
    # one gap per step completed in the window: the window opens on
    # the stamp that ends warm-up and closes on the first stamp at or
    # after --seconds
    assert detail["step_ms_samples"] == result["attempted"] > 8
    assert 1.0 <= detail["window_s"] < 1.5
    assert detail["checks"] == {"finite": True, "loss_fell": True,
                                "no_compile_in_window": True}
    m = result["metrics"]
    rate = result["attempted"] / detail["window_s"]
    assert m["tokens_per_s"]["value"] == pytest.approx(4 * 8 * rate)
    _, config, family = bench_run.load_cell("tiny-host", (BENCH, FIXTURES))
    assert m["mfu"]["value"] == pytest.approx(
        100 * family.train_flops(config, {"batch_per_chip": 4, "chips": 1,
                                          "length": 8}) * rate / 1e12)
    assert m["step_ms_p95"]["value"] >= detail["step_ms_median"] > 0
    assert m["setup_s"]["value"] > detail["setup_marks_s"]["first_step"]


def test_toy_cell_traced_line_has_layer_metrics_and_no_compile():
    # a CPU trace holds no device plane: the device readers find
    # nothing and are left out of the line, the host ones report
    result = bench_run.run_cell("tiny-host", 5, 1.0, True,
                                roots=(BENCH, FIXTURES), device=dict(CPU))
    check_line(result, ["dispatch_ms.train", "compiles_in_window"])
    assert result["metrics"]["compiles_in_window"]["value"] == 0.0
    assert result["metrics"]["dispatch_ms.train"]["value"] > 0
    assert "breakdown" not in result and "busy_s" not in result["device"]
    spans = tr.load(tr.newest_xplane(
        os.path.join(bench_run.TRACE_ROOT, "tiny-host")))["spans"]
    assert {"bench.make_batch", "bench.executor_run",
            "bench.lagged_sync"} <= {s[0] for s in spans}


def test_toy_mesh_cell_checks_residency_and_all_reduce():
    result = bench_run.run_cell("tiny-dp4", 11, 0.5, False,
                                roots=(BENCH, FIXTURES),
                                device=dict(CPU, count=4))
    check_line(result, ["mfu", "tokens_per_s", "step_ms_p95", "setup_s"])


def test_the_real_command_refuses_a_cpu_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tbase-256", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU was found" in out.stderr and "'cpu'" in out.stderr
    assert '"correct"' not in out.stdout


def test_a_device_without_a_row_of_peaks_is_an_error(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(SystemExit, match="no row for device kind"):
        bench_run.require_tpu(1, (BENCH,))
    with pytest.raises(SystemExit, match="needs 4 chips"):
        bench_run.require_tpu(4, (BENCH,))


# --------------------------------------------------------------------------
# adding a cell, a configuration and a per-layer metric as files only
# --------------------------------------------------------------------------

def test_new_cell_config_and_layer_metric_are_new_files_only(tmp_path):
    before = {}
    for base, _, files in os.walk(BENCH):
        for f in files:
            if "__pycache__" not in base:
                p = os.path.join(base, f)
                before[p] = open(p, "rb").read()
    extra = tmp_path / "extra"
    for sub in ("workloads", "configs", "layer_metrics"):
        (extra / sub).mkdir(parents=True)
    config = json.load(open(os.path.join(
        FIXTURES, "configs", "tiny-transformer.json")))
    config["builder"]["d_inner_hid"] = 48
    (extra / "configs" / "tiny-wide.json").write_text(json.dumps(config))
    cell = json.load(open(os.path.join(
        FIXTURES, "workloads", "tiny-host.json")))
    cell.update(config="tiny-wide", batch_per_chip=2)
    (extra / "workloads" / "tiny-wide-b2.json").write_text(json.dumps(cell))
    (extra / "layer_metrics" / "steps_seen.py").write_text(
        'META = {"layer": "ops", "unit": "count", "moves": "mfu",\n'
        '        "source": "program_counter", "cells": ["tiny-wide-b2"]}\n'
        "\n\ndef compute(run):\n    return run['steps']\n")
    roots = (BENCH, FIXTURES, str(extra))
    result = bench_run.run_cell("tiny-wide-b2", 3, 0.5, True, roots=roots,
                                device=dict(CPU))
    check_line(result, ["dispatch_ms.train", "compiles_in_window",
                        "steps_seen"])
    assert result["metrics"]["steps_seen"] == {
        "value": float(result["attempted"]), "unit": "count"}
    # the new metric lists its cell: another cell does not report it
    assert "steps_seen" not in bench_run.layer_readers("tiny-host", roots)
    for p, content in before.items():
        assert open(p, "rb").read() == content, p


# --------------------------------------------------------------------------
# BENCHMARK.json agrees with the files the harness reads
# --------------------------------------------------------------------------

def test_benchmark_json_matches_the_files_it_names():
    bj = benchmark_json()
    assert bj["command"] == ["python3", "benchmarks/run.py"]
    assert bj["paths"] == ["benchmarks", "tests/benchmark"]
    configs = {c["name"]: c for c in bj["configs"]}
    for c in bj["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        on_disk = json.load(open(os.path.join(REPO, c["file"])))
        assert on_disk["reduced"] == c["reduced"]
    e2e = {m["name"]: m for m in bj["end_to_end"]}
    assert set(e2e) == {"mfu", "tokens_per_s", "images_per_s",
                        "step_ms_p95", "setup_s"}
    assert sum(w["chips"] == 4 for w in bj["workloads"]) <= 1
    for w in bj["workloads"]:
        cell, config, family = bench_run.load_cell(w["name"], (BENCH,))
        assert (cell["config"], cell["chips"], cell["traffic"],
                cell["why"]) == (w["config"], w["chips"], w["traffic"],
                                 w["why"])
        assert w["config"] in configs and len(w["why"]) <= 200
        # the cell's end-to-end metrics are those without a
        # `workloads` list plus those that list it
        named = {n for n, m in e2e.items()
                 if w["name"] in m.get("workloads", [w["name"]])}
        assert named == {"mfu", "step_ms_p95", "setup_s",
                         *family.units(config, cell)}
        for name, u in family.units(config, cell).items():
            assert e2e[name]["unit"] == u["unit"]
        readers = bench_run.layer_readers(w["name"], (BENCH,))
        listed = {m["name"]: m for m in bj["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])}
        assert set(readers) == set(listed)
        for name, reader in readers.items():
            meta, entry = reader.META, listed[name]
            assert (meta["layer"], meta["unit"], meta["moves"],
                    meta["source"]) == (entry["layer"], entry["unit"],
                                        entry["moves"], entry["source"])
            assert meta["cells"] == entry.get("workloads")
            assert entry["moves"] in named


def test_peaks_json_has_the_v5e_row_and_its_source():
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    assert peaks["TPU v5 lite"] == {"bf16_flops": 197e12,
                                    "hbm_bytes_per_s": 819e9,
                                    "hbm_bytes": 16e9}
    assert "Google Cloud" in peaks["source"]
