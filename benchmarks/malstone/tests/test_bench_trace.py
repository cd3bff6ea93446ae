"""The trace reduction, the peak table and the bytes functions, on the CPU.

``data/cpu_trace.xplane.pb`` is a trace recorded on the CPU of two jobs of a
small jitted program (a scan of scatter-adds), each wrapped in the
benchmark's ``bench.job`` / ``bench.dispatch`` / ``bench.wait`` spans, with
20 ms of host sleep inside each wait; ``data/cpu_trace.hlo.txt`` is that
program's compiled HLO.
"""

import pathlib
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def op(name, start, end):
    return tr.Op(name, float(start), float(end))


@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(DATA / "cpu_trace.xplane.pb")
    hlo = tr.parse_hlo((DATA / "cpu_trace.hlo.txt").read_text())
    return trace, hlo


def test_recorded_trace_reduces(recorded):
    trace, hlo = recorded
    assert [s.name for s in trace.host_spans].count("bench.job") == 2
    s = tr.summarize(trace, hlo)
    assert 0.04 < s.window_s < 0.5           # two jobs and two 20 ms sleeps
    assert 0 < s.busy_s[0] < s.window_s
    assert 0 < s.scatter_s[0] <= s.busy_s[0]
    assert s.collective_s == {0: 0.0}
    assert any("scatter-add" in label for label, _ in s.top_ops)
    name, longest = s.gaps[0]
    assert name == "bench.wait" and longest >= 0.02


def test_parse_hlo_opcodes_and_fusions(recorded):
    _, hlo = recorded
    whiles = [i for i in hlo.values() if i.opcode == "while"]
    assert whiles and all(not tr.is_scatter(i) for i in whiles)
    fused = [n for n, i in hlo.items() if i.opcode == "fusion"
             and tr.is_scatter(i)]
    assert fused, "the scatter-add fusion is found through its computation"


def test_parse_hlo_collectives_and_tuple_shapes():
    text = """HloModule m

%fused (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %a2a = s32[4]{0} all-to-all(s32[4]{0} %p), dimensions={0}
}

ENTRY %main (x: s32[4]) -> (s32[], s32[4]) {
  %x = s32[4]{0} parameter(0)
  %w = (s32[], s32[4]{0}) while((s32[], s32[4]{0}) %t), condition=%c, body=%fused
  %f = s32[4]{0} fusion(s32[4]{0} %x), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/all_to_all"}
  ROOT %ar = s32[4]{0} all-reduce(s32[4]{0} %f), to_apply=%fused
}
"""
    hlo = tr.parse_hlo(text)
    assert hlo["w"].opcode == "while" and not tr.is_collective(hlo["w"])
    assert hlo["f"].opcode == "fusion" and tr.is_collective(hlo["f"])
    assert hlo["f"].op_name == "jit(f)/all_to_all"
    assert tr.is_collective(hlo["ar"]) and tr.is_collective(hlo["a2a"])


def test_union_clips_and_merges():
    ops = [op("a", 0, 10), op("b", 5, 15), op("c", 20, 30), op("d", 40, 50)]
    assert tr.union_ns(ops, 0, 100) == 35
    assert tr.union_ns(ops, 8, 25) == 12
    assert tr.union_ns([], 0, 10) == 0


def test_self_times_subtract_nested_ops():
    ops = [op("while", 0, 100), op("f1", 10, 30), op("f2", 40, 90),
           op("inner", 50, 60)]
    assert dict(tr.self_times(ops)) == {"while": 30, "f1": 20, "f2": 40,
                                        "inner": 10}


def test_idle_gaps_named_by_inner_host_span():
    ops = [op("a", 0, 10), op("b", 30, 40), op("c", 45, 100)]
    spans = [op("bench.job", 0, 100), op("bench.dispatch", 0, 2),
             op("bench.wait", 2, 38), op("bench.wait", 46, 100)]
    gaps = tr.idle_gaps(ops, 0, 100, spans)
    assert gaps == [("bench.wait", 20e-9), ("between host spans", 5e-9)]


def test_summarize_without_a_job_span_reads_nothing():
    trace = tr.Trace({0: [[op("a", 0, 10)]]}, {}, [])
    assert tr.summarize(trace, {}) is None


def test_peak_lookup_and_unknown_kind():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_reducer_bytes_and_roofline_share():
    assert peaks.reducer_min_bytes(1 << 20, "streams") == 20 * (1 << 20)
    assert peaks.reducer_min_bytes(1 << 20, "mapreduce") == 12 * (1 << 20)
    # 819 MB at 819 GB/s takes 1 ms: done in 2 ms it is half the roofline
    assert peaks.roofline_share(819e6, 2e-3, 819e9) == pytest.approx(50.0)
    with pytest.raises(ValueError):
        peaks.roofline_share(1.0, 0.0, 819e9)
