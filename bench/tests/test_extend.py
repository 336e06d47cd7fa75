"""A configuration, a cell and a per-layer metric are added as new files
and manifest entries alone: the harness finds them, and no file that was
there changes."""

import hashlib
import json
import pathlib
import shutil

import manifest as M

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _digests(d: pathlib.Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_cell_and_metric_are_found(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = _digests(bench)
    man = json.loads((ROOT / "BENCHMARK.json").read_text())

    # new files only
    cfg = json.loads((bench / "configs" / "dcgan.json").read_text())
    cfg.update(name="dcgan_wide", generator_channels=[2048, 1024, 512, 256, 3])
    (bench / "configs" / "dcgan_wide.json").write_text(json.dumps(cfg))
    (bench / "configs" / "dcgan_wide.py").write_text(
        (bench / "configs" / "dcgan.py").read_text())
    (bench / "traffic" / "dcgan_burst.json").write_text(json.dumps(
        {"kind": "open_loop", "arrivals": "poisson", "rate_per_s": 100.0,
         "max_batch": 64, "max_queue": 1000, "pool": 8, "sample": 8}))
    (bench / "checks" / "dcgan_wide-serve-burst.json").write_text(
        json.dumps({"out_rms_err": 0.02, "out_max_err": 0.02}))
    (bench / "metrics" / "requests_per_batch.py").write_text(
        "def read(name, rd):\n"
        "    n = rd.counters.get('steps')\n"
        "    return rd.counters['slots_real'] / n if n else None\n")

    # new manifest entries only
    man["configs"].append({"name": "dcgan_wide", "source": "assumed",
                           "file": "bench/configs/dcgan_wide.json",
                           "reduced": [], "why": "a wider generator"})
    man["workloads"].append({"name": "dcgan_wide-serve-burst",
                             "config": "dcgan_wide",
                             "traffic": "dcgan_burst", "chips": 1,
                             "why": "bursts of grids"})
    for e in man["end_to_end"]:
        if "workloads" in e and e["name"].startswith("serve_"):
            e["workloads"].append("dcgan_wide-serve-burst")
    man["per_layer"].append({"name": "requests_per_batch.serve",
                             "unit": "requests", "better": "higher",
                             "source": "host_clock", "layer": "serving",
                             "moves": "serve_p50_ms",
                             "workloads": ["dcgan_wide-serve-burst"]})
    M.validate(man)

    files = M.files_for(man, "dcgan_wide-serve-burst", bench=bench)
    assert files["config"]["generator_channels"][0] == 2048
    assert files["traffic"]["rate_per_s"] == 100.0
    assert files["driver"].__file__.endswith("drivers/open_loop.py")
    assert [p["name"] for p in M.per_layer_for(man, "dcgan_wide-serve-burst")
            ][-1] == "requests_per_batch.serve"
    reader = M.metric_reader("requests_per_batch.serve", bench=bench)

    class Rd:
        counters = {"steps": 4, "slots_real": 10}
    assert reader.read("requests_per_batch.serve", Rd()) == 2.5
    layers = files["model"].gen_layers(files["config"])
    assert layers[0].cin == 2048

    after = _digests(bench)
    assert {k: after[k] for k in before} == before
