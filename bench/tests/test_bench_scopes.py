"""The TL step's phases and the engine's spans in the trace reduction
(``bench.lib.scopes``): hand-made traces, a small trace recorded on the
chip, and the harness's own calls of ``bench.lib.trace``."""
import json
from pathlib import Path

import pytest

from bench.lib import scopes as sc
from bench.lib import trace as tr

DATA = Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _restore_trace(monkeypatch):
    """Loading a reader installs ``bench.lib.scopes`` in
    ``bench.lib.trace``; each test here leaves the base functions as it
    found them."""
    monkeypatch.setattr(tr, "extract", tr.extract)
    monkeypatch.setattr(tr, "reduce", tr.reduce)


def _recorded():
    return json.loads((DATA / "train_ds7b_3steps.json").read_text())



MS = 1_000_000          # ns


def _scoped_trace():
    """One step program call (0..140 ms) on one device, its ops carrying
    the TL step's scopes, then an op of another program; host spans of the
    engine around it."""
    j = "jit(step)/"
    ops = [
        ["fusion.1", 0, 10, j + "jvp(tl_node)/dot_general"],
        ["jvp_tl_reassembly_.1 tpu_custom_call", 10, 5,
         j + "jvp(tl_reassembly)/pallas_call"],
        ["fusion.2", 15, 20, j + "jvp(tl_tail)/checkpoint/tl_tail/dot"],
        ["fusion.3", 35, 5, j + "tl_loss/reduce_sum"],
        ["fusion.4", 40, 30, j + "transpose(jvp(tl_tail))/jvp(tl_tail)/"
         "checkpoint/rematted_computation/tl_tail/dot_general"],
        ["fusion.5", 70, 40,
         j + "transpose(jvp(tl_tail))/checkpoint/tl_tail/dot_general"],
        ["fusion.6", 110, 8, j + "transpose(jvp(tl_node))/mul"],
        ["fusion.7", 118, 12, j + "tl_optimizer/sqrt"],
        ["iota.1", 130, 2, ""],
        ["fusion.8", 136, 2, j + "transpose(jvp(pow))"],
        ["fusion.9", 150, 10, "jit(other)/tl_optimizer/sqrt"],
    ]
    dev = {"ops": [[n, s * MS, d * MS, p] for n, s, d, p in ops],
           "modules": [["jit_step(1)", 0, 140 * MS],
                       ["jit_other(2)", 150 * MS, 10 * MS]]}
    host = [["bench.window", 0, 170 * MS], ["tl_run", 0, 170 * MS],
            ["tl_step", 0, 1 * MS], ["tl_input_wait", 140 * MS, 8 * MS],
            ["tl_put_batch", 141 * MS, 2 * MS], ["tl_sync", 162 * MS, 8 * MS],
            ["tl_step", 500 * MS, 1 * MS]]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_phase_of_reads_scope_and_direction_from_the_path():
    assert sc.phase_of("jit(step)/jvp(tl_node)/dot") == ("tl_node", "fwd")
    assert sc.phase_of("transpose(jvp(tl_node))/mul") == ("tl_node", "bwd")
    assert sc.phase_of("jit(step)/transpose(jvp(jvp()))/checkpoint/"
                       "rematted_computation/tl_tail/tanh") == \
        ("tl_tail", "recompute")
    assert sc.phase_of("jit(step)/tl_optimizer/sqrt") == \
        ("tl_optimizer", "fwd")
    # the first scope names the phase; a name that merely contains tl_ is
    # not a scope
    assert sc.phase_of("jit(step)/tl_loss/tl_tail/x") == ("tl_loss", "fwd")
    assert sc.phase_of("jit(step)/shuttl_x/add") == ("other", "fwd")
    assert sc.phase_of("") == ("other", "fwd")


def test_scopes_split_the_step_programs_ops_by_phase_and_direction():
    red = sc.reduce(_scoped_trace())
    ms = {p: {d: pytest.approx(v * 1e3) for d, v in by.items()}
          for p, by in red["scopes"].items()}
    assert ms == {"tl_node": {"fwd": 10, "bwd": 8},
                  "tl_reassembly": {"fwd": 5},
                  "tl_tail": {"fwd": 20, "recompute": 30, "bwd": 40},
                  "tl_loss": {"fwd": 5},
                  "tl_optimizer": {"fwd": 12},
                  "other": {"fwd": 2, "bwd": 2}}
    # the op of another program (150..160 ms) is left out: the phases and
    # other account for every op inside the step program and nothing else
    assert sum(v for by in red["scopes"].values() for v in by.values()) \
        == pytest.approx(134e-3)


def test_idle_by_span_keeps_every_span_and_idle_gaps_the_non_zero():
    red = sc.reduce(_scoped_trace())
    idle = red["idle_by_span"]
    # idle: 132..136, 138..150 and 160..170 ms.  The put (141..143) is
    # the innermost span over its part, the wait takes the rest of
    # 140..148, the sync 162..170; tl_run takes what no shorter span covers
    assert idle["tl_put_batch"] == pytest.approx(2e-3)
    assert idle["tl_input_wait"] == pytest.approx(6e-3)
    assert idle["tl_sync"] == pytest.approx(8e-3)
    assert idle["tl_run"] == pytest.approx(10e-3)
    assert idle["tl_step"] == 0.0           # present, though it covers none
    assert "no bench span" not in idle
    assert dict(red["idle_gaps"]) == {k: v for k, v in idle.items() if v}


def test_idle_within_span_reads_the_whole_wait_under_other_spans():
    red = sc.reduce(_scoped_trace())
    within = red["idle_within_span"]
    # the put (141..143) inside the wait (140..148) takes none of it
    assert within["tl_input_wait"] == pytest.approx(8e-3)
    assert within["tl_put_batch"] == pytest.approx(2e-3)
    assert within["tl_sync"] == pytest.approx(8e-3)
    assert within["tl_run"] == pytest.approx(26e-3)
    assert within["tl_step"] == 0.0
    # program spans only
    assert "bench.window" not in within
    # a put on the prefetch thread that starts before the wait and ends
    # inside it, and a second wait that overlaps the first and runs on
    # past the start of the other program's op: the idle time inside the
    # union of the waits, counted once
    compact = _scoped_trace()
    compact["host"] += [["tl_put_batch", 139 * MS, 3 * MS],
                        ["tl_input_wait", 146 * MS, 8 * MS]]
    within = sc.reduce(compact)["idle_within_span"]
    # the waits cover 140..154; the other program's op runs from 150
    assert within["tl_input_wait"] == pytest.approx(10e-3)
    # the puts cover 139..143
    assert within["tl_put_batch"] == pytest.approx(4e-3)


def _run(red, steps):
    from bench.lib import spec
    cell = spec.resolve(spec.load_benchmark(), "train.ds7b.s1024")
    return {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
            "chips": 1, "peak": {"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9},
            "host": {"steps": steps}, "e2e": {}, "trace": red}


PHASE_READERS = ("fwd_ms.train", "recompute_ms.train", "bwd_ms.train",
                 "optimizer_ms.train", "input_wait_ms.train")


def test_phase_readers_on_a_hand_made_trace():
    from bench.lib import spec
    run = _run(sc.reduce(_scoped_trace()), steps=2)
    got = {m: spec.load_module(m).read(run) for m in PHASE_READERS}
    # per step of two
    assert got == pytest.approx({"fwd_ms.train": 20, "recompute_ms.train": 15,
                                 "bwd_ms.train": 24, "optimizer_ms.train": 6,
                                 "input_wait_ms.train": 4})
    # a program with no scopes and no engine spans: every reader is silent
    bare = _scoped_trace()
    for op in bare["devices"]["/device:TPU:0"]["ops"]:
        op[3] = "jit(step)/dot_general"
    bare["host"] = [h for h in bare["host"] if not h[0].startswith("tl_")]
    run = _run(sc.reduce(bare), steps=2)
    assert run["trace"]["scopes"] == {"other": {"fwd": pytest.approx(0.134)}}
    assert [spec.load_module(m).read(run) for m in PHASE_READERS] == \
        [None] * 5


# the reduction of the recorded three-step trace by the code that recorded
# it
GOLDEN_3STEPS = {
    "window_s": 0.850608688, "busy_s": 0.8505871970000001,
    "collective_exposed_s": 0.0,
    "kernels": {"vb_scatter": {"seconds": 0.002626182, "calls": 6.0}},
    "modules": {"jit_step": [0.283539138, 0.283517738, 0.283540538]},
    "device_ops": [["fusion", 0.483992646],
                   ["multiply_reduce_fusion", 0.16885260700000002],
                   ["convolution_bitcast_fusion", 0.124135643],
                   ["convolution_multiply_fusion", 0.019092825],
                   ["convolution_add_fusion", 0.012766639000000001],
                   ["multiply_add_fusion", 0.01129448],
                   ["reshape", 0.004910960000000001],
                   ["multiply_subtract_fusion", 0.004047394],
                   ["copy-done", 0.003866074],
                   ["subtract_subtract_fusion", 0.00375206]],
    "idle_gaps": [["no bench span", 2.1491e-05]],
    "n_devices": 1,
}


def test_recorded_trace_reduces_as_before():
    red = sc.reduce(_recorded(), dict(spec_kernels()), ("jit_step",))
    assert {k: red[k] for k in GOLDEN_3STEPS} == GOLDEN_3STEPS
    base = tr.reduce(_recorded(), dict(spec_kernels()), ("jit_step",))
    assert set(red) - set(base) == {"scopes", "idle_by_span",
                                    "idle_within_span"}
    assert {k: red[k] for k in base} == base
    # that trace predates the scopes: all of the step is other
    assert set(red["scopes"]) == {"other"}


def spec_kernels():
    from bench.lib import spec
    return spec.load_module("vb_scatter_roofline").KERNELS


def _pb(*fields) -> bytes:
    """A protobuf message from ``(field number, int | str | bytes)``."""
    def varint(v):
        out = b""
        while True:
            out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_paths_reads_the_tf_op_stat_of_event_metadata():
    def event(i, name, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, name),
                                       *((5, s) for s in stats)))))

    def stat_meta(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))

    tf_op = lambda path: _pb((1, 7), (5, path))
    tpu = _pb((2, "/device:TPU:0"), stat_meta(3, "hlo_category"),
              stat_meta(7, "tf_op"), stat_meta(9, "jit(step)/tl_optimizer/"
                                                  "sqrt:"),
              event(1, "%fusion.1 = f32[8]", _pb((1, 3), (5, "loop fusion")),
                    tf_op("jit(step)/jvp(tl_node)/dot_general:")),
              event(2, "%fusion.2 = f32[8]", _pb((1, 7), (7, 9))),
              event(3, "%copy-start.1 = f32[8]"),
              event(4, "%fusion.3 = f32[4]", tf_op("jit(step)/tl_loss/a:")),
              event(5, "%fusion.3 = f32[4]", tf_op("jit(step)/tl_tail/b:")))
    host = _pb((2, "/host:CPU"), stat_meta(7, "tf_op"),
               event(1, "%fusion.9 = f32[8]", tf_op("jit(f)/x:")))
    raw = _pb((1, tpu), (1, host))
    assert sc.op_paths(raw) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]": "jit(step)/jvp(tl_node)/dot_general",
        "%fusion.2 = f32[8]": "jit(step)/tl_optimizer/sqrt",
        "%copy-start.1 = f32[8]": "",
        # one name, two paths: which op an event is cannot be told
        "%fusion.3 = f32[4]": ""}}


def test_recorded_chip_trace_of_two_scoped_steps():
    from bench.lib import spec
    compact = json.loads((DATA / "train_ds7b_2steps_scoped.json").read_text())
    red = sc.reduce(compact, dict(spec_kernels()), ("jit_step",))
    run = _run(red, steps=2)
    got = {m: spec.load_module(m).read(run) for m in
           ("mfu.train", "vb_scatter_roofline") + PHASE_READERS}
    assert got == pytest.approx({
        "mfu.train": 41.72, "vb_scatter_roofline": 74.85,
        "fwd_ms.train": 68.97, "recompute_ms.train": 30.74,
        "bwd_ms.train": 163.34, "optimizer_ms.train": 19.00,
        # the window's first batch is waited for: 5.3 ms over two steps,
        # of which the innermost-span split gives the wait only 2.6 ms
        "input_wait_ms.train": 2.658}, rel=1e-3)
    assert red["idle_by_span"]["tl_input_wait"] == pytest.approx(2.614e-3,
                                                                 rel=1e-3)
    # the phases and other account for the step program's time, other for
    # under 1 % of it
    ops = sum(v for by in red["scopes"].values() for v in by.values())
    assert ops == pytest.approx(sum(red["modules"]["jit_step"]), rel=1e-4)
    assert sum(red["scopes"]["other"].values()) < 0.01 * ops
    assert red["idle_gaps"][0][0] == "tl_input_wait"


def test_loading_a_reader_points_the_harness_here():
    from bench.lib import spec
    base = tr.reduce
    spec.load_module("fwd_ms.train")
    assert (tr.extract, tr.reduce) == (sc.extract, sc.reduce)
    assert sc._base_reduce is base
    sc.install()                        # a second reader: no change
    assert (tr.extract, tr.reduce) == (sc.extract, sc.reduce)


def test_harness_reports_every_metric_of_the_cell_from_a_scoped_trace():
    """``bench/run.py``'s own reduction and readers, unchanged, on the
    recorded scoped trace: the cell's accepted metrics and the five new
    ones all read something."""
    from bench import run as harness
    from bench.lib import spec
    cell = spec.resolve(spec.load_benchmark(), "train.ds7b.s1024")
    readers = {m["name"]: spec.load_module(m["name"]) for m in cell.per_layer}
    out = {"compact_trace": json.loads(
               (DATA / "train_ds7b_2steps_scoped.json").read_text()),
           "host": {"steps": 2}, "e2e": {}}
    metrics, red = harness.per_layer(
        cell, out, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        readers)
    assert set(metrics) == {"mfu.train", "idle_share.train",
                            "vb_scatter_roofline", *PHASE_READERS}
    assert red["idle_gaps"][0][0] == "tl_input_wait"
