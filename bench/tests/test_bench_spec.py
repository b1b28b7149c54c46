"""BENCHMARK.json against its contract, and cells resolved by name."""
import json
import os
import re
import shutil

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    assert len(bench["command"]) <= 32
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    script = bench["command"][1]
    assert any(script.startswith(p + "/") for p in bench["paths"])
    assert len(json.dumps(bench)) <= 64 * 1024


def test_names_units_and_entry_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\t" not in w["why"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def _reports(m, cell):
    return "workloads" not in m or cell in m["workloads"]


def test_every_cell_reports_what_it_must(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for cell in cells:
        mine = [m for m in e2e.values() if _reports(m, cell)]
        assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
        assert any(_reports(m, cell) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for cell in cells:
            if _reports(m, cell):
                assert _reports(e2e[m["moves"]], cell), (m["name"], cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)


def test_layers_are_named_alike(bench):
    by_layer = {}
    for m in bench["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_at_most_half_the_cells_take_four_chips(bench):
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_configs_are_used_and_files_are_theirs(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert cfg["published"][key] != cfg[key]
            assert not key.endswith(("_dim", "_rank", "_size")) \
                or key == "vocab_size"


def test_every_cell_resolves_by_name(bench):
    for w in bench["workloads"]:
        cell = spec.resolve(bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert os.path.isfile(os.path.join(
            spec.BENCH_DIR, f"{cell.traffic['kind']}_cell.py"))
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert all(callable(r) for r in cell.readers.values())


def test_unknown_cell_raises(bench):
    with pytest.raises(KeyError):
        spec.resolve(bench, "no-such.cell")


def test_a_cell_added_as_files_and_entries_resolves(bench, tmp_path):
    root = tmp_path / "checkout"
    bench_dir = root / "bench"
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    added = json.loads((root / bench["configs"][0]["file"]).read_text())
    added["name"] = "added-model"
    (bench_dir / "configs" / "added-model.json").write_text(
        json.dumps(added))
    (bench_dir / "traffic" / "added-mix.json").write_text(
        json.dumps({"kind": "train", "seq_len": 2048}))
    (bench_dir / "metrics" / "added_metric.py").write_text(
        "def read(ctx):\n    return ctx.get('x')\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "added-model", "source": "s",
                           "file": "bench/configs/added-model.json",
                           "reduced": [], "why": "w"})
    new["workloads"].append({"name": "added-model.added-mix",
                             "config": "added-model",
                             "traffic": "added-mix", "chips": 1,
                             "why": "w"})
    new["per_layer"].append({"name": "added_metric", "unit": "%",
                             "better": "lower", "source": "host_clock",
                             "layer": "device", "moves": "tokens_per_s",
                             "workloads": ["added-model.added-mix"]})
    cell = spec.resolve(new, "added-model.added-mix", str(bench_dir))
    assert cell.config["name"] == "added-model"
    assert cell.traffic["seq_len"] == 2048
    assert cell.readers["added_metric"]({"x": 3}) == 3
    assert "added_metric" not in spec.resolve(
        new, bench["workloads"][0]["name"], str(bench_dir)).readers
