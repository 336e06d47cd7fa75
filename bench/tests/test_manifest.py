"""``BENCHMARK.json`` against its own rules, and the rules themselves."""

import copy
import json
import pathlib

import pytest

import manifest as M

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture
def man():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_manifest_is_valid(man):
    M.validate(man)
    for w in man["workloads"]:
        files = M.files_for(man, w["name"])
        assert files["traffic"]["kind"] == "open_loop"
        assert files["limits"]
    for p in man["per_layer"]:
        assert hasattr(M.metric_reader(p["name"]), "read")


def test_every_moves_target_is_reported_where_the_metric_is(man):
    for w in man["workloads"]:
        reported = {e["name"] for e in M.end_to_end_for(man, w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        for p in M.per_layer_for(man, w["name"]):
            assert p["moves"] in reported


@pytest.mark.parametrize("edit, message", [
    (lambda m: m["per_layer"][0].update(name="bad name"), "bad name"),
    (lambda m: m["per_layer"][0].update(name="µs_wait"), "bad name"),
    (lambda m: m["end_to_end"][0].update(unit="milli seconds"), "bad unit"),
    (lambda m: m["end_to_end"][0].update(workloads=["vnet-serve-poisson"]),
     "do not report"),
    (lambda m: m["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda m: [w.update(chips=4) for w in m["workloads"][:3]],
     "at most half"),
    (lambda m: m["workloads"][0].update(config="dcgan",
                                        traffic="dcgan_poisson"),
     "appears twice"),
])
def test_rules_refuse(man, edit, message):
    bad = copy.deepcopy(man)
    edit(bad)
    with pytest.raises(M.ManifestError, match=message):
        M.validate(bad)
