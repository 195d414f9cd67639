"""The reduction of a trace to device time by phase of the ensemble-BDF
step and to idle time inside the timed program, and the reader of
``in_call_idle_share.ensemble``."""
import gzip
from pathlib import Path

import pytest

from chipbench import devtrace, harness, layout, phases
from chipbench.tests.support import ROOT

DATA = Path(__file__).resolve().parent / "data"
NEWTON = ("jit(call)/jit(<lambda>)/while/body/ensemble_bdf.newton/while/"
          "body/jit(newton_residual_soa)/pallas_call")
LSETUP = ("jit(call)/jit(<lambda>)/while/body/ensemble_bdf.lsetup/cond/"
          "branch_1_fun/add")
RESCALE = ("jit(call)/jit(<lambda>)/while/body/ensemble_bdf.rescale/"
           "jit(history_rescale_soa)/pallas_call")

HLO = f"""\
%region_1.2 (arg: f32[8]) -> f32[8] {{
  %arg = f32[8]{{0}} parameter(0)
  %newton_residual_soa.5 = f32[8]{{0}} custom-call(%arg), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="{NEWTON}" stack_frame_id=106}}, backend_config={{"custom_call_config":{{"body":"TUzv"}}}}
  %history_rescale_soa.2 = f32[8]{{0}} custom-call(%arg), custom_call_target="tpu_custom_call", metadata={{op_name="{RESCALE}"}}
  %copy.1 = f32[8]{{0}} copy(%arg)
  ROOT %fusion.3 = f32[8]{{0}} fusion(%copy.1), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{LSETUP}" source_file="batched.py"}}
}}
"""


def test_op_names_and_phases():
    names = phases.op_names([HLO])
    assert names == {"newton_residual_soa.5": NEWTON,
                     "history_rescale_soa.2": RESCALE, "fusion.3": LSETUP}
    assert phases.phase(NEWTON) == "ensemble_bdf.newton"
    assert phases.phase(LSETUP) == "ensemble_bdf.lsetup"
    # the innermost scope wins; no scope at all is unscoped
    assert phases.phase("a/ensemble_bdf.newton/b/ensemble_bdf.update/c"
                        ) == "ensemble_bdf.update"
    assert phases.phase("jit(call)/jit(<lambda>)/while/cond/and") == \
        "unscoped"
    assert phases.phase("") == "unscoped"


def _op(start, end, name, module="jit_call", copy=False):
    return devtrace.Op(0, start, end, f"%{name} = f32[8]{{0}} add()",
                       copy=copy, module=module)


def _trace():
    """Two executions of the timed program, ops 1-4.5 and 6-8, the
    benchmark's own program between them (an op whose name repeats one
    of the timed program's), and an asynchronous copy of the first."""
    ops = [_op(1.0, 2.0, "newton_residual_soa.5"), _op(2.5, 3.0, "fusion.3"),
           _op(3.0, 3.5, "copy.1"), _op(3.2, 3.6, "copy-start.1", copy=True),
           _op(3.7, 4.5, "newton_residual_soa.5"),
           _op(5.2, 5.5, "fusion.3", module="jit_summarize"),
           _op(6.0, 7.0, "history_rescale_soa.2"),
           _op(7.2, 8.0, "newton_residual_soa.5")]
    spans = [devtrace.Span("bench.window", 0.0, 10.0)]
    return devtrace.Trace(ops=ops, spans=spans,
                          timed=frozenset({"jit_call"}))


def _reader():
    return layout.metric_reader(ROOT, "in_call_idle_share.ensemble")


def test_phase_time_and_idle_inside_calls():
    t, names = _trace(), phases.op_names([HLO])
    got = phases.phase_time(t, names)
    assert got == {"ensemble_bdf.newton": pytest.approx(2.6),
                   "ensemble_bdf.lsetup": pytest.approx(0.5),
                   "ensemble_bdf.rescale": pytest.approx(1.0),
                   "unscoped": pytest.approx(0.5)}
    # the phases and unscoped sum to the timed program's device time
    assert sum(got.values()) == pytest.approx(
        sum(op.end - op.start for op in t.in_window()))
    # gaps inside the calls: 2.0-2.5 (lsetup ends it), 3.6-3.7 (after
    # the copy) and 7.0-7.2 (newton); the gaps next to the other
    # program, 4.5-5.2 and 5.5-6.0, are between calls
    assert phases.in_call_idle_s(t) == pytest.approx(0.8)
    by = phases.idle_by_phase(t, names)
    assert by == {"ensemble_bdf.lsetup": pytest.approx(0.5),
                  "ensemble_bdf.newton": pytest.approx(0.3)}
    assert sum(by.values()) == pytest.approx(phases.in_call_idle_s(t))
    # an op of another program is never looked up by name
    assert phases.phase_of(t, names, t.ops[5]) == "unscoped"
    rec = harness.Record(cell=None, counters={}, samples={}, trace=t)
    assert _reader().read(rec) == pytest.approx(100.0 * 0.8 / 10.0)
    # a window that closes inside a call clips its gaps
    t.spans = [devtrace.Span("bench.window", 0.0, 7.1)]
    assert phases.in_call_idle_s(t) == pytest.approx(0.5 + 0.1 + 0.1)


def test_reader_reads_nothing_without_a_trace_of_timed_programs():
    read = _reader().read
    assert read(harness.Record(cell=None, counters={}, samples={})) is None
    empty = devtrace.Trace(ops=[], spans=[], timed=frozenset({"jit_call"}))
    assert read(harness.Record(cell=None, counters={}, samples={},
                               trace=empty)) is None
    untimed = _trace()
    untimed.timed = None
    assert read(harness.Record(cell=None, counters={}, samples={},
                               trace=untimed)) is None


def _module_intervals(path, name):
    """The executions of program ``name`` on the ``XLA Modules`` line of
    each device of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            out.extend((e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                        * 1e-9) for line in plane.lines
                       if line.name == devtrace.MODULES_LINE
                       for e in line.events
                       if e.name.split("(", 1)[0] == name)
    return out


def test_recorded_trace_of_a_program_without_scopes(tmp_path):
    """The trace recorded on a TPU v5e before the integrator named its
    phases: every op reads unscoped, the totals are the timed program's,
    and the idle inside the call is the idle inside its ``XLA Modules``
    interval but for the edges of the call.  The trace's device clock
    sits about 6 ms off the host's, so the window is widened to hold
    the call."""
    for name in ("small.xplane.pb", "small_call.hlo.txt"):
        with gzip.open(DATA / f"{name}.gz", "rb") as src:
            (tmp_path / name).write_bytes(src.read())
    hlo = (tmp_path / "small_call.hlo.txt").read_text()
    path = str(tmp_path / "small.xplane.pb")
    t = devtrace.load(path, [hlo], ["jit_call"])
    names = phases.op_names([hlo])
    assert len(names) > 500
    lo = min(op.start for op in t.ops)
    hi = max(op.end for op in t.ops)
    t.spans = [devtrace.Span("bench.window", lo, hi)]
    got = phases.phase_time(t, names)
    assert set(got) == {"unscoped"}
    ops = t.in_window()
    assert len(ops) > 10000
    assert got["unscoped"] == pytest.approx(
        sum(op.end - op.start for op in ops))
    (cs, ce), = _module_intervals(path, "jit_call")
    in_module = (ce - cs) - devtrace.union_length(
        [(op.start, op.end) for op in t.ops], cs, ce)
    idle = phases.in_call_idle_s(t)
    assert 0 < idle <= in_module < idle + 5e-6
    assert phases.idle_by_phase(t, names) == {
        "unscoped": pytest.approx(idle)}
    rec = harness.Record(cell=None, counters={}, samples={}, trace=t)
    assert _reader().read(rec) == pytest.approx(100.0 * idle / (hi - lo))
