"""Cells, configurations, traffic mixes and metrics are files found by
name: a new one is a file dropped into its directory."""
import json
import shutil

import pytest

from bench import spec


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m["name"]).read)
        assert callable(spec.generator(cell.mix["generator"]).generate)
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file()


def test_dropped_in_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((bench / "configs" / "yi-34b.json").read_text())
    cfg.update(name="new-model", reference="new_family")
    (bench / "configs" / "new-model.json").write_text(json.dumps(cfg))
    (bench / "references" / "new_family.py").write_text(
        "FAMILY = 'new'\n")
    mix = json.loads((bench / "traffic" / "chat.json").read_text())
    mix["generator"] = "fixed_gap"
    (bench / "traffic" / "steady.json").write_text(json.dumps(mix))
    (bench / "traffic" / "fixed_gap.py").write_text(
        "def generate(mix, rate, duration_s, seed):\n    return 'fixed'\n")
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    wl = json.loads((bench / "workloads" / "internlm2-20b.chat.json").read_text())
    wl.update(config="new-model", traffic="steady")
    (bench / "workloads" / "new-model.steady.json").write_text(
        json.dumps(wl))
    benchmark = {
        "workloads": [{"name": "new-model.steady", "config": "new-model",
                       "traffic": "steady", "chips": 1}],
        "end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                       {"name": "ttft_p90_ms", "unit": "ms",
                        "workloads": ["other.cell"]}],
        "per_layer": [{"name": "new_metric.steady", "unit": "%",
                       "moves": "output_tok_s"},
                      {"name": "engine_step_ms.x", "unit": "ms",
                       "moves": "ttft_p90_ms"}]}
    cell = spec.load_cell("new-model.steady", benchmark, bench)
    assert cell.config["name"] == "new-model"
    assert cell.reference.FAMILY == "new"
    assert spec.reference("new_family", bench) is cell.reference
    assert [m["name"] for m in cell.end_to_end] == ["output_tok_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric.steady"]
    assert spec.generator(cell.mix["generator"], bench).generate(
        cell.mix, 1.0, 1.0, 0) == "fixed"
    assert spec.metric_reader("new_metric.steady", bench).read(None) == 42.0


def _config_without_reference(bench):
    cfg = json.loads((bench / "configs" / "yi-34b.json").read_text())
    del cfg["reference"]
    (bench / "configs" / "no-reference.json").write_text(json.dumps(cfg))
    return spec.load_config("no-reference", bench)


def _config_naming_a_missing_reference(bench):
    cfg = json.loads((bench / "configs" / "yi-34b.json").read_text())
    cfg["reference"] = "no_such_family"
    (bench / "configs" / "lost.json").write_text(json.dumps(cfg))
    return spec.load_config("lost", bench)


@pytest.mark.parametrize("load", [
    lambda bench: spec.load_config("no-such-model", bench),
    lambda bench: spec.metric_reader("no_such_metric.chat", bench),
    lambda bench: spec.load_cell("no.such.cell", {"workloads": []}, bench),
    lambda bench: spec.reference("no_such_family", bench),
    _config_without_reference,
    _config_naming_a_missing_reference,
], ids=["config", "metric_reader", "cell", "reference",
        "config_without_reference", "config_naming_a_missing_reference"])
def test_missing_files_are_errors(tmp_path, load):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with pytest.raises(spec.SpecError):
        load(bench)


def test_cell_files_must_agree_with_the_benchmark():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    w = bench["workloads"][0]
    w["traffic"] = "burst" if w["traffic"] != "burst" else "chat"
    with pytest.raises(spec.SpecError, match="BENCHMARK.json says"):
        spec.load_cell(w["name"], bench)
