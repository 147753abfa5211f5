"""The trace reduction: interval arithmetic on hand-made traces, and the
whole reduction on a small trace recorded on the chip."""
import json
from pathlib import Path

import pytest

from bench.lib import trace as tr

DATA = Path(__file__).parent / "data"


def test_union_merges_and_clips():
    assert tr.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 11) == \
        [(1, 4), (5, 11)]
    assert tr.measure(tr.union([(0, 10), (2, 3)])) == 10


def test_subtract_leaves_only_uncovered_time():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert tr.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert tr.subtract(a, []) == a
    assert tr.subtract([], b) == []


def _hand_trace():
    # window 0..100 ns on two devices; all-gather overlapped by a fusion on
    # device 0 except for 10 ns, not overlapped at all on device 1
    dev0 = {"ops": [["fusion.1", 0, 40], ["all-gather-start.3", 30, 20],
                    ["jvp__.1 tpu_custom_call", 60, 10],
                    ["jvp__.2 tpu_custom_call", 85, 5]],
            "modules": [["jit_step(1)", 0, 70], ["jit_prefill(2)", 80, 10]]}
    dev1 = {"ops": [["fusion.1", 0, 30], ["all-gather-done.3", 30, 20],
                    ["jvp__.1 tpu_custom_call", 60, 20]],
            "modules": [["jit_step(1)", 0, 80]]}
    host = [["bench.window", 0, 100], ["bench.engine_step", 0, 75],
            ["bench.wait_for_arrival", 75, 25]]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "host": host}


def test_reduce_busy_collective_kernels_modules_and_gaps():
    red = tr.reduce(_hand_trace(),
                    kernels={"vb": (r"tpu_custom_call", "jit_step")},
                    modules=("jit_step", "jit_prefill"))
    assert red["window_s"] == pytest.approx(100e-9)
    # device 0 busy 0..50, 60..70, 85..90 = 65; device 1 0..50, 60..80 = 70
    assert red["busy_s"] == pytest.approx(67.5e-9)
    # exposed collective: device 0 40..50 = 10, device 1 30..50 = 20
    assert red["collective_exposed_s"] == pytest.approx(15e-9)
    assert red["kernels"]["vb"]["seconds"] == pytest.approx(15e-9)
    assert red["kernels"]["vb"]["calls"] == 1
    assert sorted(red["modules"]["jit_step"]) == pytest.approx([70e-9, 80e-9])
    assert red["modules"]["jit_prefill"] == pytest.approx([10e-9])
    # the kernel call at 85 runs inside jit_prefill, not jit_step
    # device 0 idles 50..60, 70..85 and 90..100 (engine step to 75)
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.engine_step"] == pytest.approx(15e-9)
    assert gaps["bench.wait_for_arrival"] == pytest.approx(20e-9)


def test_op_name_keeps_the_custom_call_target():
    assert tr.op_name('%fusion.80 = (f32[4]{0}) fusion(f32[4]{0} %p)') == \
        "fusion.80"
    assert tr.op_name('%jvp__.1 = f32[8]{0} custom-call(s32[8]{0} %c), '
                      'custom_call_target="tpu_custom_call", x') == \
        "jvp__.1 tpu_custom_call"


def _recorded():
    return json.loads((DATA / "train_ds7b_3steps.json").read_text())


def test_recorded_chip_trace_of_three_training_steps():
    from bench.lib import spec
    cell = spec.resolve(spec.load_benchmark(), "train.ds7b.s1024")
    readers = {m["name"]: spec.load_module(m["name"]) for m in cell.per_layer}
    kernels = {}
    for mod in readers.values():
        kernels.update(getattr(mod, "KERNELS", {}))
    red = tr.reduce(_recorded(), kernels, ("jit_step",))
    # three calls of the step program, 0.2835 s each, back to back
    assert red["modules"]["jit_step"] == pytest.approx([0.2835] * 3, rel=1e-3)
    assert red["busy_s"] / red["window_s"] > 0.999
    # the reassembly kernel: one forward scatter and one backward gather
    # per step
    assert red["kernels"]["vb_scatter"]["calls"] == 6
    assert red["kernels"]["vb_scatter"]["seconds"] == pytest.approx(
        2.626e-3, rel=1e-3)
    run = {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
           "chips": 1, "peak": {"bf16_flops_per_s": 197e12,
                                "hbm_bytes_per_s": 819e9},
           "host": {"steps": 3, "window_s": red["window_s"]},
           "e2e": {}, "trace": red}
    # 537 MB per step over 819 GB/s, against 0.875 ms per step
    assert readers["vb_scatter_roofline"].read(run) == pytest.approx(
        74.9, abs=0.1)
    # 23.3 TFLOP per step, 3 steps in 0.8506 s, over 197 TFLOP/s
    assert readers["mfu.train"].read(run) == pytest.approx(41.7, abs=0.1)
    assert 0 <= readers["idle_share.train"].read(run) < 0.1


def test_readers_return_nothing_where_nothing_was_traced():
    from bench.lib import spec
    bench = spec.load_benchmark()
    empty = tr.reduce({"devices": {}, "host": []},
                      {"k": (r"tpu_custom_call", None)}, ("jit_step",))
    for m in bench["per_layer"]:
        cell = spec.resolve(bench, m["workloads"][0])
        run = {"cell": cell.name, "config": cell.config,
               "traffic": cell.traffic, "chips": 1,
               "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "host": {}, "e2e": {}, "trace": empty}
        assert spec.load_module(m["name"]).read(run) is None, m["name"]


def test_mfu_train_reads_the_step_programs_device_time():
    from bench.lib import counts, spec
    cell = spec.resolve(spec.load_benchmark(), "train.ds7b.s1024")
    t = cell.traffic
    flops = counts.train_flops_per_step(cell.config, t["global_batch"],
                                        t["seq"])
    # two chips, each running the step program for 0.5 s then 0.7 s; the
    # host's window (4 s) and step count play no part
    dev = {"ops": [], "modules": [["jit_step(7)", 0, 500_000_000],
                                  ["jit_step(7)", 600_000_000, 700_000_000]]}
    red = tr.reduce({"devices": {"/device:TPU:0": dev, "/device:TPU:1": dev},
                     "host": [["bench.window", 0, 2_000_000_000]]},
                    {}, ("jit_step",))
    run = {"traffic": t, "config": cell.config, "chips": 2,
           "peak": {"bf16_flops_per_s": 197e12},
           "host": {"steps": 99, "window_s": 4.0}, "trace": red}
    reader = spec.load_module("mfu.train")
    assert reader.read(run) == pytest.approx(
        100 * flops / 0.6 / (2 * 197e12))


def test_idle_gaps_are_named_by_the_innermost_host_span():
    dev = {"ops": [["fusion.1", 0, 20], ["fusion.2", 70, 30]],
           "modules": []}
    host = [["bench.window", 0, 100], ["PjitFunction(step)", 10, 80],
            ["TransferToDevice", 30, 10]]
    red = tr.reduce({"devices": {"/device:TPU:0": dev}, "host": host})
    gaps = dict(red["idle_gaps"])
    assert gaps["TransferToDevice"] == pytest.approx(10e-9)
    assert gaps["PjitFunction(step)"] == pytest.approx(40e-9)
    assert "no bench span" not in gaps
