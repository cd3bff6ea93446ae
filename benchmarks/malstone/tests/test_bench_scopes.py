"""The program's layer scopes as the benchmark reads them: which scope each
instruction of a compiled job belongs to (``scopes.py``), the reader of
``gen_device_ms_per_chunk``, and how much of a CPU rehearsal's traced window
the scopes name.

``data/cpu_scoped_trace.*`` (made by ``record_trace.py``) is a trace, on the
CPU, of two jobs of a small scan whose steps run under the program's layer
scopes, and that program's compiled HLO.
"""

import pathlib
import sys
import types

import pytest

TESTS = pathlib.Path(__file__).resolve().parent
BENCH_DIR = TESTS.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(TESTS))

import harness  # noqa: E402
import rehearsal  # noqa: E402
import scopes  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = TESTS / "data"
GEN = harness.load_reader(BENCH_DIR, "gen_device_ms_per_chunk")

HLO = """HloModule m

%relayout (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p = (s32[], s32[8]{0}) parameter(0)
  %v = s32[8]{0} get-tuple-element((s32[], s32[8]{0}) %p), index=1
  %d = s32[8]{0} dynamic-update-slice(s32[8]{0} %v, s32[8]{0} %v, s32[] %i)
  ROOT %t = (s32[], s32[8]{0}) tuple(s32[] %i, s32[8]{0} %d)
}

ENTRY %main (x: s32[8]) -> f32[8] {
  %x = s32[8]{0} parameter(0)
  %r = s32[8]{0} copy(s32[8]{0} %x), metadata={op_name="jit(f)/malstone.read/copy"}
  %s = s32[8]{0} fusion(s32[8]{0} %r), kind=kLoop, calls=%relayout, metadata={op_name="jit(f)/malstone.read/while/body/malstone.combine/scatter-add"}
  %w = (s32[], s32[8]{0}) while((s32[], s32[8]{0}) %s), condition=%relayout, body=%relayout
  %g = s32[8]{0} get-tuple-element((s32[], s32[8]{0}) %w), index=1
  %a = s32[8]{0} add(s32[8]{0} %g, s32[8]{0} %g), metadata={op_name="jit(f)/malstone.read/while/body/malstone.combine/add"}
  %u = s32[8]{0} negate(s32[8]{0} %r), metadata={op_name="jit(f)/while/neg"}
  %z = s32[8]{0} copy(s32[8]{0} %u)
  ROOT %q = f32[8]{0} convert(s32[8]{0} %a), metadata={op_name="jit(f)/malstone.finalize/div"}
}
"""


def test_scope_of_takes_the_innermost_program_scope():
    path = ("jit(malstone_job)/jit(run_log)/malstone.read/while/body/"
            "closed_call/malstone.combine/scatter-add")
    assert scopes.scope_of(path) == "malstone.combine"
    assert scopes.scope_of("jit(f)/while/body/add") == ""
    assert scopes.scope_of("") == ""


def test_instruction_scopes_own_path_then_readers():
    owner = scopes.instruction_scopes(HLO)
    assert owner["r"] == "malstone.read"
    assert owner["s"] == "malstone.combine"       # innermost wins
    assert owner["u"] == ""                       # a path naming no scope
    assert owner["q"] == "malstone.finalize"
    # a loop XLA built, its body and what reads it: the reader's scope
    for name in ("w", "g", "d", "t"):
        assert owner[name] == "malstone.combine", name
    assert owner["z"] == ""                       # read by nothing


@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(DATA / "cpu_scoped_trace.xplane.pb")
    text = (DATA / "cpu_scoped_trace.hlo.txt").read_text()
    return trace, text


def test_recorded_program_instructions_take_their_scopes(recorded):
    _, text = recorded
    owner = scopes.instruction_scopes(text)
    assert owner["wrapped_scatter"] == "malstone.combine"
    assert owner["bitcast_gather_fusion"] == "malstone.generate"
    # XLA rewrote the running sum into reduce-windows without metadata;
    # they go to the finalize ops that read them
    windows = [n for n in owner if n.startswith("wrapped_reduce-window")]
    assert windows and {owner[n] for n in windows} == {"malstone.finalize"}


def _ctx(summary, text, traced_jobs=2, chunks=8):
    cell = types.SimpleNamespace()
    ctx = types.SimpleNamespace(
        summary=summary, traced_jobs=traced_jobs, cell=cell, mesh=None,
        source=types.SimpleNamespace(chunks_per_chip=chunks), plan=None)
    job = types.SimpleNamespace(as_text=lambda: text)
    return ctx, job


def test_gen_reader_counts_generation_by_the_compiled_job(recorded,
                                                          monkeypatch):
    trace, text = recorded
    summary = tr.summarize(trace, tr.parse_hlo(text))
    ctx, job = _ctx(summary, text)
    monkeypatch.setattr(harness, "compile_job", lambda *a: job)
    owner = scopes.instruction_scopes(text)
    gen = [(label, t) for label, t in summary.top_ops
           if owner[label.split(" ")[0]] == "malstone.generate"]
    # some generation ops' labels keep too little of their path to name
    # the scope; the compiled job's HLO still does
    assert any("malstone.generate" not in label for label, _ in gen)
    want = 1e3 * sum(t for _, t in gen) / (2 * 8)
    assert GEN(ctx) == pytest.approx(want)
    assert 0 < want < 1e3 * summary.busy_s[0] / (2 * 8)


def test_gen_reader_reads_nothing_without_generation(recorded, monkeypatch):
    trace, text = recorded
    summary = tr.summarize(trace, tr.parse_hlo(text))
    owner = scopes.instruction_scopes(text)
    others = summary._replace(top_ops=[
        x for x in summary.top_ops
        if owner[x[0].split(" ")[0]] != "malstone.generate"])
    ctx, job = _ctx(others, text)
    monkeypatch.setattr(harness, "compile_job", lambda *a: job)
    assert GEN(ctx) is None
    assert GEN(_ctx(None, text)[0]) is None
    assert GEN(_ctx(summary, text, traced_jobs=0)[0]) is None


@pytest.mark.parametrize("cell", ["streams.seed", "streams.log"])
def test_rehearsed_window_is_under_program_scopes(cell, monkeypatch):
    """At least 95% of a CPU rehearsal's traced window, by each op's own
    time, belongs to one of the program's scopes, the cell's source and
    the combine among them."""
    kept = []
    summarize = harness._summarize

    def keep(trace_dir, hlo_text):
        files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        kept.append((tr.load(files[-1]), hlo_text))
        return summarize(trace_dir, hlo_text)

    monkeypatch.setattr(harness, "_summarize", keep)
    rehearsal.rehearse(cell, trace=True)
    trace, text = kept[0]
    owner = scopes.instruction_scopes(text)
    lo, hi = tr.window(trace)
    by_scope = {}
    for line in trace.ops[0]:
        for name, t in tr.self_times(
                [o for o in line if o.start_ns >= lo and o.end_ns <= hi]):
            scope = owner.get(name, "")
            by_scope[scope] = by_scope.get(scope, 0.0) + t
    source = {"seed": "malstone.generate", "log": "malstone.read"}[
        harness.load_cell(rehearsal.ROOT, cell).traffic["source"]]
    assert {source, "malstone.combine"} <= set(by_scope)
    scoped = sum(t for s, t in by_scope.items() if s)
    assert scoped >= 0.95 * sum(by_scope.values()), by_scope
