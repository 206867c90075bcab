"""The trace reduction (`swarmbench.trace`): a small trace recorded on the
CPU (two rounds of a jitted product, each inside the harness's ``dispatch``
and ``wait_gates`` spans; ``data/cpu_round.xplane.pb``), and the interval
arithmetic on intervals laid out by hand."""
from pathlib import Path

import pytest

from swarmbench import trace

DATA = Path(__file__).parent / "data" / "cpu_round.xplane.pb"


def test_recorded_cpu_trace_has_the_harness_spans_and_no_device():
    tr = trace.load(str(DATA))
    names = [n for _, _, n in tr.spans]
    assert names == ["dispatch", "wait_gates"] * 2
    assert all(e > s for s, e, _ in tr.spans)
    starts = [s for s, _, _ in tr.spans]
    assert starts == sorted(starts)
    # a CPU trace holds no TPU plane: nothing to read, so no metric
    assert tr.ops == {}
    assert trace.summarize(tr) is None


def test_find_locates_the_one_trace(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(DATA.read_bytes())
    assert trace.find(str(tmp_path)).endswith("host.xplane.pb")
    (d / "other.xplane.pb").write_bytes(b"")
    with pytest.raises(FileNotFoundError):
        trace.find(str(tmp_path))


def test_union_and_gaps_clip_to_the_window():
    ops = [(10, 20, "a"), (15, 30, "b"), (40, 50, "a"), (95, 120, "c")]
    assert trace.union(ops, 0, 100) == [[10, 30], [40, 50], [95, 100]]
    assert trace.gaps(ops, 0, 100) == [(0, 10), (30, 40), (50, 95)]
    assert trace.gaps([], 5, 9) == [(5, 9)]


def test_gap_takes_the_label_of_the_span_it_overlaps_most():
    spans = [(0, 8, "dispatch"), (8, 30, "wait_gates"), (40, 45, "dispatch")]
    assert trace.label((5, 20), spans) == "wait_gates"
    assert trace.label((2, 9), spans) == "dispatch"
    assert trace.label((31, 39), spans) == "host"


def test_summary_of_two_devices():
    tr = trace.Trace(
        ops={0: [(0, 40, "fusion.1"), (60, 100, "all-reduce.2")],
             1: [(0, 80, "fusion.1"), (80, 100, "all-reduce.2")]},
        spans=[(0, 50, "dispatch"), (50, 100, "wait_gates")])
    s = trace.summarize(tr)
    assert s["devices"] == 2
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((80 + 100) / 2 * 1e-9)
    assert s["collective_s"] == pytest.approx((40 + 20) / 2 * 1e-9)
    assert s["op_s"]["fusion.1"] == pytest.approx(60e-9)
    assert s["op_n"]["all-reduce.2"] == 1
    assert s["idle_gaps"] == [["wait_gates", pytest.approx(20e-9)]]
    assert s["device_ops"][0][0] == "fusion.1"


@pytest.mark.parametrize("event, name", [
    ("%fused_merge_all.141 = f32[4,9408]{1,0:T(4,128)S(1)} custom-call("
     "f32[4,9408]{1,0:T(4,128)} %copy-done.433), custom_call_target="
     "\"tpu_custom_call\"", "fused_merge_all.141"),
    ("%while.26 = (s32[]{:T(128)}, f32[4,64]{1,0:T(4,128)}) while(...)",
     "while.26"),
    ("fusion.3", "fusion.3"),
])
def test_op_name_is_the_hlo_name(event, name):
    assert trace.op_name(event) == name
