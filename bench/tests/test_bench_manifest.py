"""The manifest loader and the files it finds by name."""

import copy

import pytest

from harness import manifest

GOOD = manifest.load()


def test_every_named_file_exists():
    for w in GOOD["workloads"]:
        c = manifest.cell(GOOD, w["name"])
        assert c["config_data"]["name"] == w["config"]
        assert c["mix_data"]["name"] == w["traffic"]
    for m in GOOD["end_to_end"] + GOOD["per_layer"]:
        assert callable(manifest.reader(m["name"]))
    for c in GOOD["configs"]:
        assert (manifest.ROOT / c["file"]).is_file()


def test_each_cell_reports_setup_another_e2e_metric_and_a_layer_metric():
    for w in GOOD["workloads"]:
        e2e = {m["name"] for m in manifest.metrics_for(GOOD, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_for(GOOD, w["name"], "per_layer")


def bad(path, value):
    m = copy.deepcopy(GOOD)
    where = m
    for p in path[:-1]:
        where = where[p]
    where[path[-1]] = value
    return m


@pytest.mark.parametrize("path, value", [
    (("workloads", 0, "name"), "paper fig8c"),
    (("workloads", 0, "name"), "paper/fig8c"),
    (("workloads", 0, "name"), ".hidden"),
    (("workloads", 0, "name"), "x" * 65),
    (("workloads", 0, "traffic"), "mix,a"),
    (("configs", 0, "name"), "uruv paper"),
    (("end_to_end", 1, "name"), "ops per s"),
    (("end_to_end", 1, "unit"), "ops per s"),
    (("end_to_end", 1, "unit"), "µs"),
    (("end_to_end", 1, "unit"), "x" * 17),
    (("per_layer", 0, "unit"), ""),
    (("per_layer", 0, "moves"), "not_a_metric"),
    (("per_layer", 0, "source"), "guess"),
    (("per_layer", 0, "workloads"), ["no.such.cell"]),
    (("workloads", 0, "chips"), 2),
])
def test_rejects_names_and_units_outside_the_contract(path, value):
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad(path, value))


def test_rejects_unknown_cells_and_files():
    with pytest.raises(manifest.ManifestError):
        manifest.cell(GOOD, "no.such.cell")
    with pytest.raises(manifest.ManifestError):
        manifest.reader("no.such.metric")
    with pytest.raises(manifest.ManifestError):
        manifest.reader("../harness/session")
