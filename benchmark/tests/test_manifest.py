"""The manifest loader: what is there resolves, what is not is refused."""

import json
import os
import re

import pytest

import manifest

REAL = manifest.REAL
ROOT = os.path.dirname(REAL)


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REAL)


def write(tmp_path, doc):
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    return str(path)


def real_doc():
    with open(REAL) as fh:
        return json.load(fh)


def test_every_name_in_the_manifest_resolves(man):
    for cell in man.cells.values():
        assert man.config(cell["config"])["chips"] == cell["chips"]
        traffic = man.traffic(cell["traffic"])
        assert hasattr(man.module("drivers", traffic["driver"]), "run")
        assert hasattr(man.module("operands", traffic["operand"]["name"]),
                       "make")
        for kind in traffic["requests"]:
            assert hasattr(man.module("fetches", kind["fetch"]), "take")
            for step in kind["steps"]:
                mod = man.module("steps", step["call"])
                assert all(hasattr(mod, f)
                           for f in ("bind", "plan", "traffic"))
    for name in man.metrics:
        spec = man.metric_spec(name)
        assert hasattr(man.module("readers", spec["reader"]), "read")


def test_every_cell_reports_setup_one_more_and_a_layer_metric(man):
    for cell in man.cells:
        e2e = [m["name"] for m in man.cell_metrics(cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = man.cell_metrics(cell, "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (cell, m["name"])


def test_the_contracts_limits_on_the_file(man):
    doc = man.doc
    assert sorted(doc) == sorted(["command", "paths", "run_seconds",
                                  "configs", "workloads", "end_to_end",
                                  "per_layer"])
    assert 1 <= doc["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(
        1, len(doc["workloads"]) // 2)
    for entry in doc["workloads"] + doc["configs"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in doc["configs"]:
        assert len(c["source"]) <= 200
        assert c["file"].startswith("benchmark/") and os.path.isfile(
            os.path.join(ROOT, c["file"]))
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in doc["per_layer"]}
    assert all(re.match(r"^[a-z ]+$", name) for name in layers)
    assert os.path.getsize(REAL) <= 64 << 10


def test_unknown_workload_is_refused(man):
    with pytest.raises(manifest.ManifestError, match="unknown workload"):
        man.cell("stack4d-1chip.nothing")


@pytest.mark.parametrize("name", ["has space", "a/b", "", "x" * 65, "muµ"])
def test_a_bad_name_is_refused(tmp_path, name):
    doc = real_doc()
    doc["workloads"][0]["name"] = name
    with pytest.raises(manifest.ManifestError, match="name"):
        manifest.Manifest(write(tmp_path, doc))


@pytest.mark.parametrize("unit", ["tokens per second", "µs", "", "x" * 17])
def test_a_bad_unit_is_refused(tmp_path, unit):
    doc = real_doc()
    doc["per_layer"][0]["unit"] = unit
    with pytest.raises(manifest.ManifestError, match="unit"):
        manifest.Manifest(write(tmp_path, doc))


def test_an_unknown_source_and_a_twice_named_metric_are_refused(tmp_path):
    doc = real_doc()
    doc["per_layer"][0]["source"] = "guess"
    with pytest.raises(manifest.ManifestError, match="source"):
        manifest.Manifest(write(tmp_path, doc))
    doc = real_doc()
    doc["per_layer"].append(dict(doc["per_layer"][0]))
    with pytest.raises(manifest.ManifestError, match="twice"):
        manifest.Manifest(write(tmp_path, doc))


def test_a_name_without_its_file_is_refused(tmp_path):
    doc = real_doc()
    doc["workloads"][0]["traffic"] = "no_such_mix"
    doc["configs"].append(dict(doc["configs"][0], name="ghost"))
    man = manifest.Manifest(write(tmp_path, doc))
    with pytest.raises(manifest.ManifestError, match="no traffic/"):
        man.traffic("no_such_mix")
    with pytest.raises(manifest.ManifestError, match="no configs/"):
        man.config("ghost")
    with pytest.raises(manifest.ManifestError, match="unknown config"):
        man.config("never_listed")
    with pytest.raises(manifest.ManifestError, match="unknown metric"):
        man.metric_spec("never_listed")


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert manifest.peaks("TPU v5 lite")["hbm_GBps"] == 819.0
    with pytest.raises(manifest.ManifestError, match="TPU v9"):
        manifest.peaks("TPU v9")
    with pytest.raises(manifest.ManifestError, match="not in"):
        manifest.peaks("cpu")
