"""Every cell finds its files by name, every name obeys the contract's
characters, and a cell is added by adding files and entries alone."""
import json
import re
import shutil

import pytest

from bench.lib import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_resolves_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.resolve(bench, w["name"])
        assert (spec.BENCH_DIR / "lib" / f"{cell.traffic['driver']}.py"
                ).is_file()
        assert cell.config["hidden_size"] > 0
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(spec.load_module(m["name"]).read)


def test_names_and_units_use_allowed_characters(bench):
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
             + [k for c in bench["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in bench[group]]
        assert len(got) == len(set(got)), group


def test_every_per_layer_metric_moves_a_reported_metric(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            cell = spec.resolve(bench, w)
            assert m["moves"] in {x["name"] for x in cell.end_to_end}


def test_files_under_paths_are_named_from_name_characters():
    for p in (spec.ROOT / "bench").rglob("*"):
        if "__pycache__" in p.parts:
            continue
        rel = p.relative_to(spec.ROOT).as_posix()
        assert re.fullmatch(r"[A-Za-z0-9_./-]+", rel), rel


def test_a_cell_is_added_by_files_and_entries_only(tmp_path, bench):
    """A longer-sequence mix enters as one more data file, a metric as one
    more reader, both cells as entries; no file that is there changes."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(root).as_posix(): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    mix = json.loads((root / "bench/traffic/tl_s1024_b8.json").read_text())
    mix.update(seq=4096, global_batch=2)
    (root / "bench/traffic/tl_s4096_b2.json").write_text(json.dumps(mix))
    (root / "bench/metrics/steps_echo.py").write_text(
        "def read(run):\n    return run['host']['steps']\n")
    new = json.loads(json.dumps(bench))
    new["workloads"].append(
        {"name": "train.ds7b.s4096", "config": "deepseek-7b",
         "traffic": "tl_s4096_b2", "chips": 1, "why": "long sequences"})
    new["end_to_end"][0]["workloads"].append("train.ds7b.s4096")
    new["per_layer"].append(
        {"name": "steps_echo", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "device",
         "moves": "train_tokens_per_s", "workloads": ["train.ds7b.s4096"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = spec.resolve(new, "train.ds7b.s4096", root)
    assert (cell.traffic["seq"], cell.traffic["driver"]) == (4096, "train")
    assert [m["name"] for m in cell.per_layer] == ["steps_echo"]
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s",
                                                    "setup_s"}
    old = spec.resolve(new, "train.ds7b.s1024", root)
    assert "steps_echo" not in {m["name"] for m in old.per_layer}
    reader = spec.load_module("steps_echo", root)
    assert reader.read({"host": {"steps": 7}}) == 7
    after = {p.relative_to(root).as_posix(): p.read_bytes()
             for p in (root / "bench").rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items())
