"""The trace reduction on a hand-built trace in the layout of a TPU
profile: a ``/device:TPU:0`` plane with ``XLA Modules`` and ``XLA Ops``
lines, and the harness's host spans on ``/host:CPU``.  Times in us."""
from pathlib import Path

import pytest

from bench import trace_reduce

# (line, name, start_us, end_us)
DEVICE = [("XLA Modules", "jit_decode(1)", 10, 40),
          ("XLA Ops", "fusion.1", 10, 20),
          ("XLA Ops", "dot.2", 25, 40),
          ("XLA Modules", "jit_scan(7)", 60, 70),
          ("XLA Ops", "while.3", 60, 70),
          ("XLA Modules", "jit_scan(7)", 120, 130),   # after the window
          ("XLA Ops", "while.3", 120, 130)]
HOST = [("window", 0, 100), ("engine.run", 5, 50),
        ("client.wait_arrival", 52, 95)]


def _plane(pid, name, events):
    names = sorted({e[1] for e in events})
    meta = {n: i + 1 for i, n in enumerate(names)}
    lines = {}
    for line, n, a, b in events:
        lines.setdefault(line, []).append(
            f"events {{ metadata_id: {meta[n]} offset_ps: {a * 10**6} "
            f"duration_ps: {(b - a) * 10**6} }}")
    body = "".join(
        f'lines {{ id: {i + 1} name: "{line}" timestamp_ns: 0 '
        + " ".join(evs) + " } "
        for i, (line, evs) in enumerate(lines.items()))
    body += "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }} ' for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}" {body}}}\n'


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData
    text = (_plane(1, "/device:TPU:0", DEVICE)
            + _plane(2, "/host:CPU", [("python", *h) for h in HOST]))
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.reduce_trace(str(path),
                                     ("engine.run", "client.wait_arrival"))


def test_busy_is_the_union_of_ops_in_the_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(100e-6)
    assert reduced["busy_s"] == pytest.approx(35e-6)


def test_device_ops_named_by_program(reduced):
    got = [(n, round(t * 1e6, 6)) for n, t in reduced["device_ops"]]
    assert got == [("jit_decode(1)/dot.2", 15), ("jit_decode(1)/fusion.1", 10),
                   ("jit_scan(7)/while.3", 10)]


def test_idle_gaps_named_by_host_span(reduced):
    got = [(n, round(t * 1e6, 6)) for n, t in reduced["idle_gaps"]]
    assert got == [("client.wait_arrival", 30), ("engine.run", 20),
                   ("engine.run", 10), ("engine.run", 5)]


def test_union():
    assert trace_reduce.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [
        (0, 4), (6, 7)]


def test_modules_stand_in_where_a_plane_has_no_ops(tmp_path):
    from jax.profiler import ProfileData
    device = [e for e in DEVICE if e[0] == "XLA Modules"]
    text = (_plane(1, "/device:TPU:0", device)
            + _plane(2, "/host:CPU", [("python", *h) for h in HOST]))
    path = tmp_path / "m.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    r = trace_reduce.reduce_trace(str(path), ())
    assert r["busy_s"] == pytest.approx(40e-6)
    assert [n for n, _ in r["device_ops"]] == ["jit_decode(1)/",
                                               "jit_scan(7)/"]


# A trace recorded on a TPU v5e by ``record_trace.py`` (the tiny offline
# cell, a 2-second window): the line names, the op names and the clock
# shared by device ops and the host's ``window`` span as the chip has them.
CHIP_TRACE = (Path(__file__).resolve().parents[1] / "testdata"
              / "tiny_offline.xplane.pb")


@pytest.fixture(scope="module")
def chip():
    return trace_reduce.reduce_trace(str(CHIP_TRACE),
                                     ("engine.run", "client.wait_arrival"))


def test_chip_trace_busy_within_the_window(chip):
    assert chip["devices"] == 1
    assert 1.5 < chip["window_s"] < 3.0
    assert 0 < chip["busy_s"] < 0.01 * chip["window_s"]


def test_chip_trace_ops_named_by_program_and_instruction(chip):
    names = [n for n, _ in chip["device_ops"]]
    assert len(names) == 10
    for n in names:
        prog, op = n.split("/")
        assert prog.startswith("jit_") and op and " " not in op


def test_chip_trace_idle_gaps_fall_in_engine_run(chip):
    gaps = chip["idle_gaps"]
    assert [n for n, _ in gaps] == ["engine.run"] * 10
    assert sum(t for _, t in gaps) < chip["window_s"]
