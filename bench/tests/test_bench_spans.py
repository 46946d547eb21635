"""The engine's spans in a profiler trace, and their reduction
(``bench.trace_spans``): on a CPU trace of the tiny offline cell, on a
hand-built trace in the layout of a TPU profile (times in us), and on
traces recorded on a TPU v5e."""
import collections
import glob
import time
from pathlib import Path

import jax
import pytest

from bench import harness
from bench import trace_spans as ts
from bench.tests import tiny
from bench.tests.test_bench_trace import _plane

# Each span and the span that must hold it.
PARENT = {"engine.admit": {"engine.step"},
          "engine.prefill": {"engine.admit"},
          "kv.write": {"engine.admit"},
          "engine.plan_decode": {"engine.step"},
          "engine.decode": {"engine.step"},
          "kv.gather": {"engine.decode"},
          "model.decode_step": {"engine.decode"},
          "kv.scatter": {"engine.decode"},
          "engine.sync": {"engine.admit", "kv.write", "kv.scatter",
                          "engine.decode"},
          "engine.retire": {"engine.step"}}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One wave of the tiny offline cell served under the profiler, after
    the harness's set-up wave: the engine, and its spans as
    ``(start_ns, end_ns, name)``."""
    from jax.profiler import ProfileData
    tmp = tmp_path_factory.mktemp("spans")
    cell = harness.load_cell("tiny.offline", tiny.make_root(tmp / "root"))
    _, client = harness.build(cell, seed=0)
    wave = harness.gen.wave(cell.traffic, 0, 1, cell.config["serve"]["slots"],
                            cell.sizes["vocab_size"])
    with jax.profiler.trace(str(tmp / "trace")):
        client.serve([(r, time.perf_counter()) for r in wave])
    path = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    spans = [(e.start_ns, e.end_ns, e.name)
             for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name in ts.ENGINE_SPANS]
    return client.eng, spans


def test_cpu_trace_spans_nest(served):
    _, spans = served
    assert {n for _, _, n in spans} == set(ts.ENGINE_SPANS)
    for a, b, name in spans:
        holders = [s for s in spans if s[0] <= a and b <= s[1]
                   and s != (a, b, name)]
        if name == "engine.step":
            assert not holders
            continue
        parent = min(holders, key=lambda s: s[1] - s[0])[2]
        assert parent in PARENT[name], (name, parent)


def test_cpu_trace_span_counts(served):
    eng, spans = served
    n = collections.Counter(name for _, _, name in spans)
    c = eng.stats()["metrics"]["counters"]
    assert n["engine.step"] == len(eng.step_log) == c["steps"]
    assert n["engine.admit"] == n["kv.write"] == c["admissions"] > 0
    assert (n["engine.decode"] == n["kv.gather"] == n["kv.scatter"]
            == n["model.decode_step"] == c["decode_batches"]
            == eng.decode_batches > 0)
    assert n["engine.sync"] == c["host_syncs"] \
        == 2 * c["admissions"] + 2 * c["decode_batches"]


# A hand-built trace.  Device: busy 14-22, 34-44, 56-74 and 95-100 of the
# window 0-100.  Host: an admission step 10-50 and a pure-decode step
# 50-90 inside ``engine.run`` 10-90.
DEVICE = [("XLA Modules", "jit_prefill(1)", 14, 22),
          ("XLA Ops", "while.1", 14, 22),
          ("XLA Modules", "jit_decode_step(2)", 34, 44),
          ("XLA Ops", "fusion.2", 34, 44),
          ("XLA Modules", "jit_kv_gather(3)", 56, 60),
          ("XLA Ops", "copy.3", 56, 60),
          ("XLA Modules", "jit_decode_step(2)", 60, 70),
          ("XLA Ops", "fusion.2", 60, 70),
          ("XLA Modules", "jit_kv_scatter(4)", 70, 74),
          ("XLA Ops", "copy.4", 70, 74),
          ("XLA Modules", "jit_kv_scatter(4)", 95, 105),
          ("XLA Ops", "copy.4", 95, 105)]
HOST = [("window", 0, 100), ("engine.run", 10, 90),
        ("engine.step", 10, 50),
        ("engine.admit", 12, 30), ("engine.prefill", 12, 16),
        ("engine.sync", 16, 24), ("kv.write", 24, 30),
        ("engine.decode", 32, 48), ("model.decode_step", 32, 36),
        ("engine.sync", 36, 48),
        ("engine.step", 50, 90),
        ("engine.plan_decode", 50, 54), ("engine.decode", 54, 80),
        ("kv.gather", 54, 58), ("model.decode_step", 58, 62),
        ("kv.scatter", 62, 66), ("engine.sync", 66, 80),
        ("engine.retire", 84, 88)]


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData
    text = (_plane(1, "/device:TPU:0", DEVICE)
            + _plane(2, "/host:CPU", [("python", *h) for h in HOST]))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return ts.reduce_spans(str(path))


def _us(d):
    return {n: round(t * 1e6, 6) for n, t in d.items()}


def test_program_time_by_stable_name(reduced):
    assert _us(reduced["program_s"]) == {
        "jit_decode_step": 20, "jit_kv_scatter": 9, "jit_prefill": 8,
        "jit_kv_gather": 4}
    assert list(reduced["program_s"])[0] == "jit_decode_step"


def test_idle_split_over_innermost_spans(reduced):
    # A gap splits across the spans it passes through (22-34: the
    # admission's sync and write, the step, the decode's dispatch); on a
    # tie of starts the shorter span holds (10-12 is engine.step's).
    assert reduced["window_s"] == pytest.approx(100e-6)
    assert reduced["idle_s"] == pytest.approx(59e-6)
    idle = {n: round(v["idle_s"] * 1e6, 6)
            for n, v in reduced["idle_in"].items()}
    assert idle == {"window": 15, "engine.run": 0, "engine.step": 12,
                    "engine.admit": 0, "engine.prefill": 2,
                    "engine.sync": 12, "kv.write": 6,
                    "model.decode_step": 2, "engine.plan_decode": 4,
                    "kv.gather": 2, "engine.decode": 0, "kv.scatter": 0,
                    "engine.retire": 4}
    within = {n: round(v["idle_within_s"] * 1e6, 6)
              for n, v in reduced["idle_in"].items()}
    assert within["engine.admit"] == 10         # 12-14 and 22-30
    assert within["engine.decode"] == 14        # 32-34, 44-48, 54-56, 74-80
    assert within["engine.step"] == 22 + 22     # 10-14, 22-34, 44-56, 74-90
    assert within["engine.run"] == 59 - 15
    assert within["window"] == 15
    counts = {n: v["count"] for n, v in reduced["idle_in"].items()}
    assert counts["engine.step"] == 2 and counts["engine.sync"] == 3
    assert counts["window"] == 0 and counts["engine.run"] == 1


def test_idle_by_span_largest_first(reduced):
    got = [(n, round(t * 1e6, 6)) for n, t in reduced["idle_by_span"]]
    assert got[0] == ("window", 15)
    assert [t for _, t in got] == sorted((t for _, t in got), reverse=True)
    assert sum(t for _, t in got) == pytest.approx(59)
    assert all(t > 0 for _, t in got)


def test_pure_decode_steps(reduced):
    # Only the second step decodes without an admission: idle 50-56 and
    # 74-90.
    assert reduced["pure_decode"]["steps"] == 1
    assert reduced["pure_decode"]["idle_s"] == pytest.approx(22e-6)


def test_program_names_lose_their_fingerprint():
    assert ts.program("jit_kv_gather(13862870630551598027)") \
        == "jit_kv_gather"
    assert ts.program("jit__lambda_14033273097715399490_") == "jit__lambda"
    assert ts.program("jit_decode_step") == "jit_decode_step"


def test_innermost_names_every_piece():
    pieces = ts.innermost([(2, 8, "a"), (3, 5, "b"), (5, 9, "c")], (0, 10))
    assert pieces == [(0, 2, "window"), (2, 3, "a"), (3, 5, "b"),
                      (5, 8, "c"), (8, 9, "c"), (9, 10, "window")]


# The trace ``record_trace.py`` recorded on a TPU v5e before the engine
# opened spans: all its idle time lies in the harness's ``engine.run``.
OLD_CHIP_TRACE = (Path(__file__).resolve().parents[1] / "testdata"
                  / "tiny_offline.xplane.pb")


def test_trace_without_engine_spans_reduces():
    red = ts.reduce_spans(str(OLD_CHIP_TRACE))
    assert set(red["idle_in"]) <= {"engine.run", "window"}
    assert red["idle_in"]["engine.run"]["idle_s"] > 0.99 * red["idle_s"]
    assert red["pure_decode"] == {"idle_s": 0.0, "steps": 0}
    assert "jit_decode_step" not in red["program_s"]


# The tiny offline cell recorded on a TPU v5e by ``record_trace.py`` with
# the engine's spans (a 2-second window; the file renamed).
CHIP_TRACE = OLD_CHIP_TRACE.with_name("tiny_offline_spans.xplane.pb")


@pytest.fixture(scope="module")
def chip():
    return ts.reduce_spans(str(CHIP_TRACE))


def test_chip_trace_programs_have_stable_names(chip):
    for name in ("jit_decode_step", "jit_kv_gather", "jit_kv_scatter",
                 "jit_kv_write"):
        assert chip["program_s"][name] > 0, name
    assert not any(n.startswith("jit__lambda") for n in chip["program_s"])


def test_chip_trace_idle_falls_in_program_spans(chip):
    inside = sum(v["idle_s"] for n, v in chip["idle_in"].items()
                 if n in ts.ENGINE_SPANS)
    assert inside >= 0.9 * chip["idle_s"] > 0
    n = {name: v["count"] for name, v in chip["idle_in"].items()}
    assert n["engine.sync"] == 2 * n["engine.admit"] + 2 * n["engine.decode"]
    assert n["kv.gather"] == n["kv.scatter"] == n["engine.decode"] > 0
    assert 0 < chip["pure_decode"]["steps"] < n["engine.step"]
