"""BENCHMARK.json and the files it names hold together, and the validator
finds each kind of fault."""

import copy

import pytest

from benchmark import manifest


def test_manifest_and_its_files_hold_together():
    assert manifest.validate(manifest.load()) == []


def broken(edit):
    bench = copy.deepcopy(manifest.load())
    edit(bench)
    return manifest.validate(bench)


@pytest.mark.parametrize("edit, needle", [
    (lambda b: b["workloads"][0].update(name="has space"), "bad name"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per second"), "bad unit"),
    (lambda b: b["end_to_end"][0].update(unit="x" * 17), "bad unit"),
    (lambda b: b["per_layer"][0].update(moves="no_such_metric"), "moves"),
    (lambda b: b["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda b: b["end_to_end"].pop(-1), "setup_s"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0], name="new.cell")),
     "no workloads/new.cell.json"),
    (lambda b: b["configs"].append(dict(b["configs"][0], name="unused")),
     "used by no cell"),
    (lambda b: b["workloads"][0].update(chips=2), "chips=2"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_validator_finds(edit, needle):
    assert any(needle in fault for fault in broken(edit))


def test_layer_metric_must_move_a_metric_its_cell_reports():
    def edit(b):
        b["end_to_end"].append({"name": "other_s", "unit": "s",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock", "workloads": []})
        next(m for m in b["per_layer"] if m["name"] == "mfu")["moves"] \
            = "other_s"
    assert any("does not report" in f for f in broken(edit))


def test_a_metric_file_says_only_how_the_metric_is_read(tmp_path, monkeypatch):
    """BENCHMARK.json alone says what a metric is and which cells report
    it, so a new cell joins a metric without any existing file changing."""
    for m in manifest.load()["per_layer"]:
        assert set(manifest.metric_file(m["name"])) <= {"reader", "args"}
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "mfu.json").write_text(
        '{"reader": "mfu", "unit": "%"}')
    monkeypatch.setattr(manifest, "HERE", tmp_path)
    assert any("mfu: its file holds" in f
               for f in manifest.validate(manifest.load()))


def test_every_cell_finds_its_files_by_name():
    bench = manifest.load()
    for c in bench["workloads"]:
        wl = manifest.workload_file(c["name"])
        assert wl["config"] == c["config"]
        assert (manifest.HERE / "drivers" / f"{wl['driver']}.py").is_file()
    for m in bench["per_layer"]:
        assert manifest.metric_file(m["name"])["reader"]
