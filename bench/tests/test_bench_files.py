"""Every file the benchmark names loads by name, and a cell the harness
was not written against needs only files of its own."""
import json
import os

import pytest

from bench import harness

SPEC = harness.load_spec()


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_has_its_files(cell):
    files = harness.Files()
    wl = files.workload(cell)
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert wl["config"] == entry["config"]
    assert wl["traffic"] == entry["traffic"]
    assert files.config(wl["config"])["torch_dtype"] == "bfloat16"
    assert files.traffic(wl["traffic"])["loop"] == "closed"
    assert callable(files.driver(wl["driver"]).run)
    assert wl["check"]["served_logit_gap"] > 0
    # every cell reports set-up, another end-to-end metric and a layer's
    e2e = [m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(SPEC, cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_each_per_layer_metric_has_a_reader(metric):
    assert callable(harness.Files().metric(metric).read)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_each_config_file_is_where_the_benchmark_says(cfg):
    path = os.path.join(harness.ROOT, cfg["file"])
    with open(path) as f:
        body = json.load(f)
    assert body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    assert harness.Files().config(cfg["name"]) == body


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A workload, a configuration and a traffic mix that the harness has
    never seen, in a directory of their own, load by name beside the
    benchmark's own drivers and metric readers."""
    for kind, name, body in (
            ("configs", "newcfg", {"hidden_size": 32}),
            ("traffic", "newmix", {"loop": "closed", "clients": 1}),
            ("workloads", "newcfg.newmix",
             {"config": "newcfg", "traffic": "newmix", "driver": "serve"})):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / f"{name}.json").write_text(json.dumps(body))
    files = harness.Files(str(tmp_path), harness.BENCH)
    ctx = harness.Ctx(files, "newcfg.newmix", 1, 1.0, False, 0.0)
    assert ctx.config == {"hidden_size": 32}
    assert ctx.traffic["clients"] == 1
    assert files.driver(ctx.workload["driver"]) is not None
    spec = dict(SPEC, workloads=SPEC["workloads"] + [
        {"name": "newcfg.newmix", "config": "newcfg", "traffic": "newmix",
         "chips": 1}])
    e2e = [m["name"] for m in harness.cell_metrics(spec, "newcfg.newmix",
                                                   "end_to_end")]
    assert e2e == ["setup_s"]
    with pytest.raises(FileNotFoundError):
        files.workload("no.such.cell")
