"""``BENCHMARK.json`` against the benchmark's contract, every name resolved
to its file, and a new cell added by new files and entries alone."""

import hashlib
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in DOC["workloads"]]


def test_top_level_keys_and_command():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["bench"]
    assert DOC["command"] == ["python3", "bench/run.py"]
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_text_fields():
    names = [c["name"] for c in DOC["configs"]] + CELLS + [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        spec.check_name(n)
    for w in DOC["workloads"]:
        spec.check_name(w["config"])
        spec.check_name(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    texts = [c["why"] for c in DOC["configs"]] + [c["source"] for c in DOC["configs"]]
    texts += [m["layer"] for m in DOC["per_layer"]] + DOC["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    for c in DOC["configs"]:
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            spec.check_name(k)
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert spec.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for bad in ("a b", "a,b", "a/b", ".x", "µs", "x" * 65):
        with pytest.raises(ValueError):
            spec.check_name(bad)


def test_entries_have_exactly_the_contract_keys():
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in DOC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in DOC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_every_cell_resolves_and_reports_what_it_must():
    bench = spec.Benchmark(str(ROOT))
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    for name in CELLS:
        cell = bench.cell(name)
        assert callable(cell.system.LOOPS[cell.traffic["loop"]])
        assert callable(cell.reference.initial_state)
        got = {m.name for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            moved = e2e[DOC_METRIC[m.name]["moves"]]
            assert "workloads" not in moved or name in moved["workloads"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(m.reader.read)


DOC_METRIC = {m["name"]: m for m in DOC["per_layer"]}


def test_config_files_hold_what_the_entry_says():
    for c in DOC["configs"]:
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
        for width in ("n_block", "mg"):
            assert width not in c["reduced"]


def _tree_hashes(root: Path) -> dict:
    return {p: hashlib.sha256(p.read_bytes()).hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric; no file that is there changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree_hashes(tmp_path / "bench")
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "karman_3072x12288.json").read_text())
    cfg.update(name="karman_dummy", nx=512, ny=2048)
    (b / "configs" / "karman_dummy.json").write_text(json.dumps(cfg))
    (b / "traffic" / "snap5.json").write_text(json.dumps({"loop": "snapshot", "advected_cells_per_snapshot": 5}))
    (b / "layer_metrics" / "snapshots_per_window.py").write_text("def read(run):\n    return len(run.saves) or None\n")
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({"name": "karman_dummy", "source": "https://arxiv.org/abs/1807.06534",
                           "file": "bench/configs/karman_dummy.json", "reduced": ["nx", "ny"], "why": "test"})
    doc["workloads"].append({"name": "cfd_dummy_snap5", "config": "karman_dummy", "traffic": "snap5", "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:  # the loop's end-to-end metrics list the new cell
        if "cfd_karman_snap" in m.get("workloads", ()):
            m["workloads"].append("cfd_dummy_snap5")
    doc["per_layer"].append({"name": "snapshots_per_window", "unit": "count", "better": "higher", "source": "host_clock",
                             "layer": "staging", "moves": "save_stall_s", "workloads": ["cfd_dummy_snap5"]})
    # a split of a metric that is there needs no reader of its own
    doc["per_layer"].append({"name": "device_idle.dummy", "unit": "%", "better": "lower", "source": "device_trace",
                             "layer": "device", "moves": "save_stall_s", "workloads": ["cfd_dummy_snap5"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = spec.Benchmark(str(tmp_path)).cell("cfd_dummy_snap5")
    assert cell.config["nx"] == 512 and cell.traffic["advected_cells_per_snapshot"] == 5
    assert {"snapshots_per_window", "device_idle.dummy"} <= {m.name for m in cell.per_layer}
    assert {m.name for m in cell.end_to_end} == {"setup_s", "loop_steps_per_s", "save_stall_s"}
    after = _tree_hashes(b)
    assert all(after[p] == h for p, h in before.items())


def test_unknown_names_are_errors():
    bench = spec.Benchmark(str(ROOT))
    with pytest.raises(KeyError):
        bench.cell("no_such_cell")
    doc = json.loads(json.dumps(DOC))
    doc["per_layer"].append({"name": "no_reader_file", "unit": "s", "better": "lower", "source": "host_clock",
                             "layer": "device", "moves": "setup_s"})
    with pytest.raises(FileNotFoundError):
        spec.Benchmark(str(ROOT), doc)


def test_gitignore_keeps_run_leftovers_out():
    ignored = (ROOT / ".gitignore").read_text().split()
    for entry in (".jax_cache/", "__pycache__/"):
        assert entry in ignored
    assert not re.search(r"^/?bench/", "\n".join(ignored), re.M)
