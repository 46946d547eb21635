"""A later change adds a configuration, a traffic mix, a cell or a metric by
adding files and entries: the harness finds each by its name."""
import json

import pytest

from bench import harness
from bench.tests import tiny


def test_every_declared_cell_resolves():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert harness.reference(cell).make_params
        for trace in (False, True):
            ms = harness.metrics_for(cell, trace)
            assert ms, (w["name"], trace)
            for m in ms:
                assert callable(harness.metric_reader(cell, m["name"]))
    names = {m["name"] for m in bench["end_to_end"]}
    assert names == {"tokens_per_s", "setup_s"}


def test_new_files_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    (root / "bench" / "metrics" / "prompt_tokens.py").write_text(
        "def read(run):\n"
        "    return sum(r['prompt_len'] for r in run.requests)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "prompt_tokens.tps", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": ["tiny.offline"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("tiny.offline", root)
    assert cell.config["model"] == tiny.TINY_MODEL
    assert cell.traffic == tiny.TINY_MIXES["tiny-offline"]
    names = [m["name"] for m in harness.metrics_for(cell, trace=True)]
    assert "prompt_tokens.tps" in names
    read = harness.metric_reader(cell, "prompt_tokens.tps")
    run = harness.RunRecord(
        sizes=cell.sizes, setup_s=1.0, window_s=1.0,
        requests=[{"prompt_len": 5}, {"prompt_len": 7}], steps=[],
        decode_wall_s=0.0, decode_batches=0, compiles=0,
        memory_peak_bytes=None, peaks=None)
    assert read(run) == 12
    open_cell = harness.load_cell("tiny.open", root)
    assert [m["name"] for m in harness.metrics_for(open_cell, False)] == [
        "ttft_p90_ms", "tpot_p90_ms", "setup_s"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no-such.cell")
