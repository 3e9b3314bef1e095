"""BENCHMARK.json and the files it names: every name resolves to a file of
its own, and each reader agrees with its entry."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench")]

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_finds_its_files(cell):
    cfg = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert (ROOT / cfg["file"]).is_file()
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()
    assert (ROOT / "bench" / "limits" / f"{cell['name']}.json").is_file()
    e2e = harness.cell_metrics(SPEC, cell["name"], "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert harness.cell_metrics(SPEC, cell["name"], "per_layer")


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_every_configuration_finds_what_it_names(entry):
    """The reference beside the file and its ops module (or the default)."""
    import check
    import model as bench_model
    path = ROOT / entry["file"]
    cj = bench_model.load_config(path)
    assert callable(check.load_reference(path, cj).forward)
    ops = bench_model.ops_module(path, cj)
    assert callable(ops.sequence_flops) and isinstance(ops.KERNELS, dict)
    bench_model.served_config(cj)


@pytest.mark.parametrize("entry", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader_that_agrees(entry):
    """One reader per quantity, found by the name; what it moves is an
    end-to-end metric that every cell it lists reports."""
    mod = harness.reader(entry, ROOT)
    assert callable(mod.read)
    assert not any(hasattr(mod, k) for k in ("UNIT", "SOURCE", "LAYER", "MOVES"))
    if "moves" in entry:
        for cell in entry["workloads"]:
            e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")}
            assert entry["moves"] in e2e, cell


def test_every_reader_file_is_read_by_an_entry():
    quantities = {m["name"].split(".")[0] for k in ("end_to_end", "per_layer")
                  for m in SPEC[k]}
    files = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    assert files == quantities
