"""``bench/program_trace.py``: the program's spans and the device's named
scopes in a traced run, checked by hand and against ``bench/trace.py``."""
from pathlib import Path

import numpy as np
import pytest

from bench import harness, program_trace, trace
from bench.program_trace import ProgramReduction

DATA = Path(__file__).resolve().parent / "data"
READERS = ("route_ms.batch", "predict_device_ms", "predict_roofline",
           "device_idle_share.batch")


def _synthetic():
    """A route step and a predict call, with program spans and ops under
    the ``bucket`` and ``gather`` scopes (seconds, then ns)."""
    ns = 1e9
    P = "/device:TPU:0"
    dev = [[P, "%while.1", 20, 60, "jit(p)/bucket/while"],
           [P, "%fusion.2", 25, 50, "jit(p)/bucket/while/body/scatter-add"],
           [P, "%fusion.3", 60, 70, "jit(p)/gather/mul"],
           [P, "%copy.4", 70, 72, "jit(p)/convert_element_type"]]
    prog = [["engine.apply", 2, 8, {}], ["engine.leaf_map", 8, 9.5, {}],
            ["engine.upload", 11, 18, {"bytes": 1000}],
            ["engine.dispatch", 18, 19, {"bytes": 24}],
            ["engine.fetch", 19, 74, {}],
            ["engine.apply", 76, 96, {}]]
    return {"device": [[p, n, a * ns, b * ns] for p, n, a, b, _ in dev],
            "device_scope": [d[4] for d in dev],
            "host": [["bench:window", 0, 100 * ns],
                     ["bench:route", 0, 10 * ns],
                     ["bench:predict", 10 * ns, 75 * ns],
                     ["bench:route", 75 * ns, 100 * ns]],
            "program": [["repro:" + n, a * ns, b * ns, st]
                        for n, a, b, st in prog]}


def test_program_readers_by_hand():
    r = ProgramReduction(_synthetic())
    assert r.busy_s == 52.0 and r.window_s == 100.0
    assert r.scope_busy_s("bucket") == 40.0     # 20-60 and 25-50 overlap
    assert r.scope_busy_s("gather") == 10.0
    assert r.scope_busy_s("while") == 40.0      # any path component
    assert r.scope_busy_s("bucket", 30e9, 40e9) == 10.0
    assert r.scope_times("gather", "predict") == [10.0]
    assert r.scope_times("bucket", "route") == [0.0, 0.0]
    assert not r.has_scope("buck")              # whole components only
    n = r.numbers()
    assert n["apply_ms.batch"] == pytest.approx(13e3)      # (6 + 20) / 2
    assert n["leaf_map_ms.batch"] == pytest.approx(1.5e3)
    assert n["upload_ms.predict"] == pytest.approx(7e3)
    # the bytes staged, and those handed to a kernel that stages its own
    assert n["h2d_mb.predict"] == pytest.approx(1.024e-3)
    assert n["bucket_device_ms"] == pytest.approx(40e3)
    assert n["gather_device_ms"] == pytest.approx(10e3)
    # the first route step holds apply and leaf_map, the second apply
    assert n["route_split_ms"] == pytest.approx(
        {"engine.batch_key": 0.0, "engine.apply": 13e3,
         "engine.weights": 0.0, "engine.leaf_map": 0.75e3})


def test_idle_split_by_program_spans_by_hand():
    r = ProgramReduction(_synthetic())
    gaps = r.idle_gaps(10 ** 6)
    # idle 0-20 and 72-100, cut at every program span's ends inside them
    want = [["engine.apply", 20.0], ["engine.upload", 7.0],
            ["engine.apply", 6.0], ["route", 4.0], ["route", 2.0],
            ["route", 2.0], ["engine.fetch", 2.0], ["engine.leaf_map", 1.5],
            ["predict", 1.5], ["engine.dispatch", 1.0],
            ["engine.fetch", 1.0]]
    assert sorted(gaps, key=lambda g: (-g[1], g[0])) == \
        sorted(want, key=lambda g: (-g[1], g[0]))
    assert sum(v for _, v in gaps) == pytest.approx(r.window_s - r.busy_s)
    n = r.numbers()
    assert n["idle_s_by_span"]["engine.apply"] == 26.0
    assert n["idle_share_in_program_spans"] == pytest.approx(38.5 / 48)


def _calls(red):
    """Calls standing in for the harness's, one per predict span."""
    return [harness.Call("predict", np.zeros((4096, 1)), 0.0, 0.097 * i,
                         1.0 * i, bytes=int(1e6) * i)
            for i in range(1, len(red.span_times("predict")) + 1)]


def _old_synthetic():
    from bench.tests.test_yardstick import _synthetic as old
    return old()


@pytest.mark.parametrize("events", [
    _old_synthetic,
    lambda: trace.load_events(DATA / "trace_predict_2calls.json")])
def test_without_program_spans_reduces_as_trace(events):
    """Events with no program spans or scopes reduce exactly as
    ``trace.Reduction`` reduces them, and the benchmark's readers read
    the same numbers from either."""
    ev = events()
    old, new = trace.Reduction(ev), ProgramReduction(ev)
    assert new.busy_s == old.busy_s and new.idle_share == old.idle_share
    for span in ("predict", "route"):
        assert new.span_times(span) == old.span_times(span)
    assert new.top_ops() == old.top_ops()
    assert new.idle_gaps() == old.idle_gaps()
    assert new.idle_gaps(10 ** 6) == old.idle_gaps(10 ** 6)
    for name in READERS:
        read = harness.load("metrics", name).read
        assert read(harness.RunData(_calls(old), new, "TPU v5 lite")) == \
            read(harness.RunData(_calls(old), old, "TPU v5 lite"))
    n = new.numbers()
    for k in ("apply_ms.batch", "leaf_map_ms.batch", "upload_ms.predict",
              "h2d_mb.predict", "bucket_device_ms", "gather_device_ms",
              "idle_share_in_program_spans"):
        assert n[k] is None, k


def test_extract_keeps_program_spans(jax_cpu, tmp_path):
    """On the CPU there is no device plane: the program's spans and their
    stats are kept, the harness's spans as ``trace.extract`` keeps them."""
    from repro.obs.trace import span
    d = str(tmp_path / "t")
    jax_cpu.profiler.start_trace(d)
    with jax_cpu.profiler.TraceAnnotation("bench:window"):
        with span("engine.upload", bytes=4096):
            jax_cpu.numpy.ones(8).block_until_ready()
    jax_cpu.profiler.stop_trace()
    ev = program_trace.extract(d)
    assert ev["host"] == trace.extract(d)["host"]
    assert len(ev["device_scope"]) == len(ev["device"])
    (name, a, b, stats), = ev["program"]
    assert name == "repro:engine.upload" and stats == {"bytes": 4096}
    (_, wa, wb), = ev["host"]
    assert wa <= a <= b <= wb


def test_traced_window_on_cpu(jax_cpu, tiny):
    """A traced run of the predict cell at a tiny size, through the
    harness: every predict call stages only its batch's factors (the label
    table is built once, in set-up), and the route step splits into the
    engine's routing spans."""
    res, events, red = program_trace.traced_run(
        "covertype.oos-predict", 2 ** 31 + 9, 0.3,
        devices=lambda c: jax_cpu.devices()[:c], sizes=tiny)
    assert res["correct"] and res["failed"] == 0
    assert res["device"]["window_s"] == red.window_s
    assert res["breakdown"]["idle_gaps"] == red.idle_gaps()
    assert events["program"] and len(events["device_scope"]) == \
        len(events["device"])
    n = red.numbers()
    assert len(red.span_times("predict")) == res["attempted"] > 0
    # per call: the batch's factors (gl int64, q float64)
    rows, T = tiny["traffic"]["batch_rows"], 10
    want = 2 * rows * T * 8 / 1e6
    assert n["h2d_mb.predict"] == pytest.approx(want)
    assert n["upload_ms.predict"] > 0 and n["apply_ms.batch"] > 0
    assert n["leaf_map_ms.batch"] > 0
    # the routing spans lie inside the harness's route step
    split = sum(n["route_split_ms"].values())
    assert 0 < split <= res["metrics"]["route_ms.batch"]["value"]
    # no device plane on the CPU: nothing to read there
    assert n["bucket_device_ms"] is None
    # the harness's own reductions are back in place
    assert (trace.extract, trace.Reduction) != \
        (program_trace.extract, program_trace.ProgramReduction)


XSPACE = '''
planes {
  id: 1  name: "/device:TPU:0"
  lines { id: 1  name: "XLA Ops"  timestamp_ns: 2000
    events { metadata_id: 7  offset_ps: 0  duration_ps: 5000000 }
    events { metadata_id: 8  offset_ps: 6000000  duration_ps: 1000000 }
    events { metadata_id: 9  offset_ps: 7000000  duration_ps: 1000000 } }
  event_metadata { key: 7  value { id: 7  name: "%fusion.1 = f32[4] fusion()"
    stats { metadata_id: 2  str_value: "jit(p)/bucket/while/body/scatter-add:" } } }
  event_metadata { key: 8  value { id: 8  name: "%fusion.2 = f32[4] fusion()"
    stats { metadata_id: 4  int64_value: 12 }
    stats { metadata_id: 2  ref_value: 3 } } }
  event_metadata { key: 9  value { id: 9  name: "%copy.3 = f32[4] copy()" } }
  stat_metadata { key: 2  value { id: 2  name: "tf_op" } }
  stat_metadata { key: 3  value { id: 3  name: "jit(p)/gather/mul:" } }
  stat_metadata { key: 4  value { id: 4  name: "flops" } }
}
planes {
  id: 2  name: "/host:CPU"
  lines { id: 1  name: "python"  timestamp_ns: 1000
    events { metadata_id: 1  offset_ps: 0  duration_ps: 10000000 }
    events { metadata_id: 2  offset_ps: 1000000  duration_ps: 2000000
             stats { metadata_id: 5  int64_value: 4096 } } }
  event_metadata { key: 1  value { id: 1  name: "bench:window" } }
  event_metadata { key: 2  value { id: 2  name: "repro:engine.upload" } }
  stat_metadata { key: 5  value { id: 5  name: "bytes" } }
}
'''


def test_extract_reads_op_names_from_event_metadata(jax_cpu, tmp_path):
    """A device op's ``op_name`` is the ``tf_op`` stat of its event
    *metadata*, a string or a reference to a stat name; the program's
    spans keep their own stats."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        jax_cpu.profiler.ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ev = program_trace.extract(str(tmp_path))
    assert [n for _, n, _, _ in ev["device"]] == \
        ["%fusion.1", "%fusion.2", "%copy.3"]
    assert ev["device_scope"] == ["jit(p)/bucket/while/body/scatter-add:",
                                  "jit(p)/gather/mul:", ""]
    assert ev["program"] == [["repro:engine.upload", 2000.0, 4000.0,
                              {"bytes": 4096}]]
    assert ev["host"] == trace.extract(str(tmp_path))["host"]
    r = ProgramReduction(ev)
    assert r.scope_busy_s("bucket") == pytest.approx(5e-6)
    assert r.scope_busy_s("gather") == pytest.approx(1e-6)
    assert r.busy_s == pytest.approx(7e-6)


def test_op_scopes_drop_ambiguous_names(jax_cpu, tmp_path):
    """Two operations with one event name and different ``op_name``s give
    that name no scope, rather than either one's."""
    txt = XSPACE.replace('name: "%copy.3 = f32[4] copy()"',
                         'name: "%fusion.1 = f32[4] fusion()" '
                         'stats { metadata_id: 2 ref_value: 3 }')
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        jax_cpu.profiler.ProfileData.text_proto_to_serialized_xspace(txt))
    scopes = program_trace.op_scopes(str(path))
    assert set(scopes) == {"/device:TPU:0"}
    assert scopes["/device:TPU:0"] == {"%fusion.1 = f32[4] fusion()": "",
                                       "%fusion.2 = f32[4] fusion()":
                                       "jit(p)/gather/mul:"}


def test_recorded_chip_trace_with_program_spans():
    """Two covertype.oos-predict calls traced on a TPU v5e with the
    program's spans and the segment product's scopes."""
    ev = trace.load_events(DATA / "trace_predict_2calls_program.json")
    r = ProgramReduction(ev)
    predicts = r._harness("predict")
    assert len(predicts) == 2
    device = [d for _, d in r.span_times("predict")]
    bucket = r.scope_times("bucket", "predict")
    gather = r.scope_times("gather", "predict")
    for d, b, g in zip(device, bucket, gather):
        assert 0.9 * d <= b + g <= d
        assert g < 0.05 * d
    # every upload lies inside a predict call and stages its bytes
    ups = [p for p in r.program if p[0] == "repro:engine.upload"]
    assert len(ups) == 2
    for _, a, b, st in ups:
        assert any(pa <= a and b <= pb for pa, pb in predicts)
        assert st["bytes"] == 172_153_600
    # the route step is the engine's routing spans, within 10%
    for (a, b), *parts in zip(r._harness("route"),
                              *(r.program_per_span(n, "route")
                                for n in program_trace.ROUTE)):
        if b <= r.t1:
            assert sum(w for p in parts for w, _ in p) >= \
                0.9 * (b - a) * 1e-9
    # idle pieces sum to the idle time, nearly all of it in program spans
    gaps = r.idle_gaps(10 ** 6)
    assert sum(v for _, v in gaps) == pytest.approx(r.window_s - r.busy_s)
    assert r.numbers()["idle_share_in_program_spans"] >= 0.9
    assert not {"predict", "other"} & {k for k, _ in r.idle_gaps()}
