"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files and manifest entries; the harness finds each by its
name, with no edit to a file that is there."""

import json
import pathlib

from benchmarks.chip import common, run


def test_new_config_mix_and_metric_are_found(tmp_path: pathlib.Path):
    chip = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "metrics"):
        (chip / d).mkdir(parents=True)
    (chip / "configs" / "toy-4f.json").write_text(json.dumps(
        {"name": "toy-4f", "driver": "offload", "aperture": [8, 8]}))
    (chip / "traffic" / "toy-bursts.json").write_text(json.dumps(
        {"kind": "closed_bursts", "category": "fft", "burst": 2}))
    (chip / "metrics" / "toy_share_pct.toy.py").write_text(
        "def read(ctx):\n    return 100.0 * ctx['frames'] / ctx['calls']\n")
    (chip / "metrics" / "toy_silent.toy.py").write_text(
        "def read(ctx):\n    return None\n")
    manifest = {
        "configs": [{"name": "toy-4f",
                     "file": "benchmarks/chip/configs/toy-4f.json"}],
        "workloads": [{"name": "toy-cell", "config": "toy-4f",
                       "traffic": "toy-bursts", "chips": 1}],
        "end_to_end": [
            {"name": "frames_per_s", "unit": "frames/s",
             "workloads": ["toy-cell"]},
            {"name": "tokens_per_s", "unit": "tokens/s",
             "workloads": ["elsewhere"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "toy_share_pct.toy", "unit": "%",
             "moves": "frames_per_s", "workloads": ["toy-cell"]},
            {"name": "toy_silent.toy", "unit": "%",
             "moves": "frames_per_s"},
            {"name": "other.serve", "unit": "%", "moves": "tokens_per_s"}],
    }
    cell = common.cell(manifest, "toy-cell", root=tmp_path)
    assert cell["config"]["aperture"] == [8, 8]
    assert cell["mix"]["burst"] == 2
    assert [m["name"] for m in cell["end_to_end"]] == ["frames_per_s",
                                                      "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == ["toy_share_pct.toy",
                                                     "toy_silent.toy"]
    got = run.per_layer(cell, {"frames": 3, "calls": 4}, base=chip)
    # a reader that finds nothing leaves its metric out of the line
    assert got == {"toy_share_pct.toy": {"value": 75.0, "unit": "%"}}


def test_every_manifest_entry_has_its_files():
    manifest = common.load_manifest()
    for wl in manifest["workloads"]:
        cell = common.cell(manifest, wl["name"])
        common.load_json(f"limits/{wl['name']}.json", common.HERE)
        assert (common.HERE / "drivers"
                / f"{cell['config']['driver']}.py").is_file()
        for m in cell["per_layer"]:
            assert (common.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert cell["end_to_end"][-1]["name"] == "setup_s"
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
