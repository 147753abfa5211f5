"""The step program's collectives in the trace reduction
(``bench.lib.collectives``): which op events are collectives, their time
and the part of it that no other op hides, split by TL phase; and the
four-chip cell's traced run, which reads every per-layer metric it lists."""
import pytest

from bench.lib import collectives as co
from bench.lib import trace as tr

MS = 1_000_000          # ns


@pytest.fixture(autouse=True)
def _restore_trace(monkeypatch):
    """Loading a reader installs ``bench.lib.collectives`` in
    ``bench.lib.trace``; each test here leaves the base functions as it
    found them."""
    monkeypatch.setattr(tr, "extract", tr.extract)
    monkeypatch.setattr(tr, "reduce", tr.reduce)


def test_collectives_are_told_by_their_hlo_text():
    # as the TPU's compiler emits them for the (2, 2) step (described-v5e
    # compile of train.ds67b.tp4)
    yes = ["%all-reduce.5 = f32[8,1024,8192]{2,1,0} all-reduce(%x)",
           "%async-collective-start = (bf16[4,128,4096], bf16[4,128,8192]) "
           "fusion(%bitcast.864), kind=kCustom, calls=%fused_computation.461",
           "%async-collective-done.1 = bf16[32,128,8192] fusion(%g)",
           "%fusion.8 = f32[4144,11008]{1,0} fusion(%g), kind=kCustom, "
           "calls=%all-reduce-scatter.4",
           "%collective-permute-done.3 = f32[48,11008] "
           "collective-permute-done(%collective-permute-start.3)",
           "%all-to-all.1 = f32[8,1024,2,4096] all-to-all(%c)",
           "%psum.7 = f32[8,1024,8192] all-reduce(%reshape_reshape.30)"]
    no = ["%fusion.361 = (bf16[8,1024,4,128,1]) fusion(%g), kind=kOutput, "
          "calls=%fused_computation.470",
          "%convolution_add_fusion = f32[8,1024,8192] fusion(%a, %b)",
          "jvp_tl_reassembly_.1 tpu_custom_call", "copy-start.114",
          # consumers of a collective's result
          "%concatenate.51 = f32[4192,11008]{1,0:T(8,128)} concatenate("
          "%collective-permute-done.3, %fusion.8)",
          "%add_fusion = f32[8,1024,8192] fusion(%all-reduce.5, %x), "
          "kind=kLoop, calls=%fused_computation.12"]
    assert all(co.is_collective(t) for t in yes)
    assert not any(co.is_collective(t) for t in no)


def _mesh_trace():
    """Two chips, one step program call each (0..100 ms), then an op of
    another program.  Collectives overlap compute on chip 0 for 5 ms."""
    j = "jit(step)/"

    def dev(shift):
        ops = [
            ["fusion.1", 0, 30, j + "jvp(tl_node)/dot_general", False],
            ["async-collective-done", 30, 10,
             j + "jvp(tl_node)/bsd,de->bse/dot_general", True],
            ["async-collective-start", 40, 2, "", True],
            ["all-reduce.1", 42, 8,
             j + "jvp(tl_tail)/tl_tail/bse,ed->bsd/dot_general", True],
            ["fusion.2", 50, 30, j + "transpose(jvp(tl_tail))/tl_tail/dot",
             False],
            ["fusion.8", 80 - shift, 15,
             j + "transpose(jvp(tl_tail))/tl_tail/dot_general", True],
            ["fusion.3", 95, 5, j + "tl_optimizer/sqrt", False],
            ["all-reduce.9", 120, 10, "jit(other)/psum", True],
        ]
        return {"ops": [[n, s * MS, d * MS, p, c] for n, s, d, p, c in ops],
                "modules": [["jit_step(1)", 0, 100 * MS],
                            ["jit_other(2)", 120 * MS, 10 * MS]]}
    return {"devices": {"/device:TPU:0": dev(5), "/device:TPU:1": dev(0)},
            "host": [["bench.window", 0, 140 * MS]]}


def test_collective_split_times_the_step_programs_collectives():
    red = co.reduce(_mesh_trace())
    c = red["collectives"]
    # 35 ms of collectives per chip inside the step program; the other
    # program's all-reduce is left out
    assert c["seconds"] == pytest.approx(35e-3)
    # chip 0's reduce-scatter starts under fusion.2 (75..80 ms)
    assert c["exposed_s"] == pytest.approx(32.5e-3)
    ms = {p: {d: pytest.approx(v * 1e3) for d, v in by.items()}
          for p, by in c["scopes"].items()}
    assert ms == {"tl_node": {"fwd": 10}, "tl_tail": {"fwd": 8, "bwd": 15},
                  "other": {"fwd": 2}}
    # what the scopes and the base reduction give is kept
    assert red["scopes"]["tl_tail"]["bwd"] == pytest.approx(45e-3)
    assert "collective_exposed_s" in red


def test_collective_readers_per_chip_and_step():
    from bench.lib import spec
    cell = spec.resolve(spec.load_benchmark(), "train.ds67b.tp4")
    readers = {m: spec.load_module(m) for m in ("collective_ms.train",
                                                "collective_exposed.train")}
    assert tr.reduce is co.reduce
    run = {"traffic": cell.traffic, "config": cell.config, "chips": 2,
           "host": {"steps": 2}, "trace": tr.reduce(_mesh_trace())}
    got = {m: r.read(run) for m, r in readers.items()}
    assert got == pytest.approx({"collective_ms.train": 17.5,
                                 "collective_exposed.train": 16.25})
    # a trace reduced without this module: both readers are silent
    run["trace"] = co.sc.reduce(_mesh_trace())
    assert [r.read(run) for r in readers.values()] == [None, None]


def test_the_four_chip_cell_reads_every_metric_it_lists():
    """The harness's own per-layer step for ``train.ds67b.tp4``, its readers
    loaded as ``bench/run.py`` loads them, on a hand-made four-chip trace:
    every metric that lists the cell gives a number."""
    import importlib.util
    from bench.lib import spec
    where = importlib.util.spec_from_file_location(
        "bench_run", spec.ROOT / "bench" / "run.py")
    harness = importlib.util.module_from_spec(where)
    where.loader.exec_module(harness)
    cell = spec.resolve(spec.load_benchmark(), "train.ds67b.tp4")
    readers = {m["name"]: spec.load_module(m["name"]) for m in cell.per_layer}
    compact = _mesh_trace()
    for dev in compact["devices"].values():
        dev["ops"].insert(1, ["jvp_tl_reassembly_.1 tpu_custom_call",
                              29 * MS, 1 * MS,
                              "jit(step)/jvp(tl_reassembly)/pallas_call",
                              False])
    compact["devices"].update({f"/device:TPU:{i}": compact["devices"][
        "/device:TPU:0"] for i in (2, 3)})
    compact["host"] += [["tl_input_wait", 100 * MS, 20 * MS]]
    out = {"compact_trace": compact, "host": {"steps": 1}, "e2e": {}}
    metrics, red = harness.per_layer(
        cell, out, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        readers)
    assert set(metrics) == set(readers)
    assert metrics["collective_exposed.train"]["value"] <= \
        metrics["collective_ms.train"]["value"]
