"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
finds its file: a configuration, a traffic mix with its loop, a reader
per per-layer metric. A new mix is data: adding one needs new files and
entries, and no edit of a file that exists."""
import hashlib
import json
import math
import re
import shutil

import pytest

from chipbench import harness, traffic

BENCH = harness.manifest()
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}
# widths a cut may never touch: sizes, dims, ranks, latents, expansion
# factors, experts per token (the vocabulary may be a chip's slice)
WIDTH = re.compile(r"(_size$|_dim$|_rank$|latent|expan|experts_per_tok)")
SLICEABLE = {"vocab_size"}


def _names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[key]:
            yield e["name"]


def test_top_level_and_entry_keys():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    for c in BENCH["configs"]:
        assert set(c) == CONFIG_KEYS
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in BENCH["workloads"]:
        assert set(w) == CELL_KEYS
    for m in BENCH["end_to_end"]:
        assert set(m) <= E2E_KEYS and set(m) - {"workloads"} == E2E_KEYS - {
            "workloads"}
    for m in BENCH["per_layer"]:
        assert set(m) == LAYER_KEYS
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_units_and_text_fields_are_legal():
    names = list(_names())
    assert len(names) == len(set(names))
    for n in names:
        assert harness.NAME.match(n), n
    for w in BENCH["workloads"]:
        assert harness.NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert harness.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in harness.SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for c in BENCH["configs"]:
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert harness.NAME.match(k), k
            assert k in SLICEABLE or not WIDTH.search(k), k


def test_every_name_finds_its_file():
    root = harness.ROOT
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        conf = harness.load_json(root / c["file"])
        assert conf["name"] == c["name"]
        for k in c["reduced"]:
            assert k in conf
        harness.model_dims(conf)
    for w in BENCH["workloads"]:
        tf = harness.load_json(harness.traffic_file(w["traffic"]))
        assert harness.kind_file(tf["kind"]).is_file()
        assert any(c["name"] == w["config"] for c in BENCH["configs"])
    for m in BENCH["per_layer"]:
        mod = harness.load_module(harness.metric_file(m["name"]), "t_" +
                                  m["name"].replace(".", "_"))
        assert callable(mod.read)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_moves_are_reported_where_the_metric_is():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        target = e2e[m["moves"]].get("workloads", cells)
        for w in m["workloads"]:
            assert w in cells and w in target, (m["name"], w)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
    for w in cells:
        reported = harness.cell_metrics(BENCH, w, False)
        assert ("setup_s", "s") in reported and len(reported) >= 2
        assert harness.cell_metrics(BENCH, w, True)


def test_run_seconds_fit_a_full_check():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert 2 + 14 * 24 * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def _digest(tree):
    return {p.relative_to(tree).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest() for p in sorted(tree.rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_mix_is_data_only(tmp_path):
    """A dummy mix of an existing kind, added as a file plus a workload
    entry, is found by name and read by the general generator; no file
    that was there changes."""
    chip = tmp_path / "chip"
    shutil.copytree(harness.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(chip)
    chat = harness.load_json(harness.traffic_file("chat", chip))
    dummy = dict(chat, rate_rps=0.5, about="a dummy mix")
    dummy["prompt"] = {"dist": "uniform", "min": 64, "max": 128}
    (chip / "traffic" / "dummy.json").write_text(json.dumps(dummy))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "danube-dummy",
                               "config": "h2o-danube-1.8b",
                               "traffic": "dummy", "chips": 1,
                               "why": "test"})
    after = _digest(chip)
    assert {k: v for k, v in after.items() if k in before} == before
    cell = harness.cell(bench, "danube-dummy")
    tf = harness.load_json(harness.traffic_file(cell["traffic"], chip))
    assert harness.kind_file(tf["kind"], chip).is_file()
    sched = traffic.open_schedule(tf, 10, 1, 32000)
    assert all(64 <= len(r["prompt"]) <= 128 for r in sched)
    with pytest.raises(harness.BenchError):
        harness.cell(bench, "no-such-cell")


def test_percentile_is_nearest_rank():
    assert harness.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert harness.percentile([1, math.inf], 95) == math.inf
