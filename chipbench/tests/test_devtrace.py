"""The reduction from a profiler trace to device numbers."""
import gzip
from pathlib import Path

import pytest

from chipbench import devtrace

DATA = Path(__file__).resolve().parent / "data"

FUSED = """\
%fused_computation.7 (param_0.1: f32[1024,6], param_1.2: f32[8]) -> f32[1024,5] {
  %param_0.1 = f32[1024,6]{1,0:T(8,128)} parameter(0)
  %slice.3 = f32[1024,5]{1,0:T(8,128)} slice(%param_0.1), slice={[0:1024], [1:6]}
  %param_1.2 = f32[8]{0:T(128)S(1)} parameter(1)
  ROOT %multiply.4 = f32[1024,5]{1,0:T(8,128)} multiply(%slice.3, %slice.3)
}

%fused_computation.9 (param_0.5: f32[6,3,1024], param_1.6: f32[1,3,1024]) -> f32[6,3,1024] {
  %param_0.5 = f32[6,3,1024]{2,1,0:T(4,128)} parameter(0)
  %param_1.6 = f32[1,3,1024]{2,1,0:T(4,128)} parameter(1)
  %constant.1 = s32[]{:T(128)} constant(0)
  ROOT %dynamic-update-slice.2 = f32[6,3,1024]{2,1,0:T(4,128)} dynamic-update-slice(%param_0.5, %param_1.6, %constant.1, %constant.1, %constant.1)
}
"""


def test_type_bytes_skips_vmem_and_counts_tuples():
    assert devtrace.type_bytes("f32[3,1024]{1,0:T(4,128)}") == 3 * 1024 * 4
    assert devtrace.type_bytes("f32[3,1024]{1,0:T(4,128)S(1)}") == 0
    assert devtrace.type_bytes("(s32[8]{0}, pred[16]{0:T(1024)(128)(4,1)})"
                               ) == 8 * 4 + 16


def test_op_bytes_of_a_kernel_and_of_fusions():
    spmv = ("%blockdiag_spmv_soa.5 = f32[3,1024]{1,0:T(4,128)} custom-call("
            "f32[3,3,1024]{2,1,0:T(4,128)} %a, f32[3,1024]{1,0:T(4,128)} %b)"
            ", custom_call_target=\"tpu_custom_call\"")
    assert devtrace.op_bytes(spmv) == (9 + 3 + 3) * 1024 * 4
    fusions = devtrace.parse_fusions(FUSED)
    sliced = ("%fusion.1 = f32[1024,5]{1,0:T(8,128)} fusion(f32[1024,6]{1,0:"
              "T(8,128)} %x, f32[8]{0:T(128)S(1)} %y), kind=kLoop, "
              "calls=%fused_computation.7")
    # the fusion reads only the 5 sliced columns; the VMEM operand is free
    assert devtrace.op_bytes(sliced, fusions) == 2 * 1024 * 5 * 4
    assert devtrace.op_bytes(sliced) == 1024 * 11 * 4
    dus = ("%fusion.2 = f32[6,3,1024]{2,1,0:T(4,128)} fusion(f32[6,3,1024]{"
           "2,1,0:T(4,128)} %h, f32[1,3,1024]{2,1,0:T(4,128)} %u), "
           "kind=kLoop, calls=%fused_computation.9")
    # updated in place: the update is read, and written over the history
    assert devtrace.op_bytes(dus, fusions) == 2 * 3 * 1024 * 4
    # control flow and the issue of an asynchronous copy are not counted
    assert devtrace.op_bytes("%while.1 = (f32[8]{0}) while((f32[8]{0}) "
                             "%t), condition=%c, body=%b") is None
    assert devtrace.op_bytes("%copy-start.2 = (f32[8]{0}, f32[8]{0:S(1)}, "
                             "u32[]{:S(2)}) copy-start(f32[8]{0} %x)") is None


def test_leaves_drop_control_flow():
    evs = [(0.0, 10.0, "while"), (1.0, 2.0, "a"), (2.0, 6.0, "cond"),
           (2.5, 3.0, "b"), (7.0, 9.0, "c"), (11.0, 12.0, "d")]
    assert [e[2] for e in devtrace._leaves(evs)] == ["a", "b", "c", "d"]


def test_union_and_gaps():
    iv = [(1.0, 3.0), (2.0, 4.0), (6.0, 7.0), (9.0, 12.0)]
    assert devtrace.union_length(iv, 0.0, 10.0) == pytest.approx(5.0)
    assert devtrace.idle_gaps(iv, 0.0, 10.0) == [(0.0, 1.0), (4.0, 6.0),
                                                 (7.0, 9.0)]


def _trace():
    ops = [devtrace.Op(0, 1.0, 2.0, "%k.1 = f32[1000]{0} custom-call("
                       "f32[1000]{0} %x)"),
           devtrace.Op(0, 2.5, 4.0, "%k.1 = f32[1000]{0} custom-call("
                       "f32[1000]{0} %x)"),
           devtrace.Op(1, 1.0, 3.0, "%k.1 = f32[1000]{0} custom-call("
                       "f32[1000]{0} %x)")]
    spans = [devtrace.Span("bench.window", 0.0, 5.0),
             devtrace.Span("bench.generate", 0.0, 1.0),
             devtrace.Span("bench.call", 1.0, 4.5)]
    return devtrace.Trace(ops=ops, spans=spans)


def test_busy_idle_roofline_breakdown():
    t = _trace()
    assert t.busy_s() == {0: pytest.approx(2.5), 1: pytest.approx(2.0)}
    share, flagged = t.hbm_roofline(8000.0)   # 8000 B/s: 1 s per op
    assert share == pytest.approx(100.0 * 3 / 4.5) and flagged == []
    share, flagged = t.hbm_roofline(1000.0)
    assert flagged and flagged[0][0] == "k.1"
    assert t.top_ops(1) == [["k.1", pytest.approx(4.5 / 2)]]
    gaps = t.top_gaps(3)
    assert gaps[0] == ["bench.call", pytest.approx(2.0)]
    assert ["bench.generate", pytest.approx(1.0)] in gaps


def test_only_timed_programs_count_in_roofline_and_top_ops():
    """An op of the benchmark's own program (here one that declares far
    more bytes than it reads) is busy time, not the system's work."""
    t = _trace()
    for op in t.ops:
        op.module = "jit_call"
    t.ops.append(devtrace.Op(0, 4.0, 4.5, "%gather.1 = f32[8]{0} gather("
                             "f32[100000]{0} %y, s32[8]{0} %i)",
                             module="jit_summarize"))
    t.ops.append(devtrace.Op(0, 0.5, 0.6, "%k.2 = f32[8]{0} custom-call("
                             "f32[8]{0} %x)"))
    whole = t.hbm_roofline(8000.0)
    t.timed = frozenset({"jit_call"})
    assert t.busy_s()[0] == pytest.approx(3.1)
    assert t.hbm_roofline(8000.0) == (pytest.approx(100.0 * 3 / 4.5), [])
    assert whole[1][0][0] == "gather.1"
    assert [name for name, _ in t.top_ops()] == ["k.1"]


def test_ops_take_the_program_whose_interval_holds_them():
    ops = [devtrace.Op(0, s, s + 0.1, "%x = f32[1]{0} add()")
           for s in (0.5, 1.5, 2.5, 3.5)]
    devtrace._attribute(ops, [(1.0, 2.0, "jit_call"), (3.0, 4.0, "jit_b")])
    assert [op.module for op in ops] == ["", "jit_call", "", "jit_b"]


def test_recorded_trace(tmp_path):
    """One call of a 1024-system ensemble to t = 1, traced on a TPU v5e
    (``data/``, gzip-compressed).  The trace's device clock sits a few
    milliseconds off the host's (about 6 ms here), which the window of
    a run, tens of seconds, does not feel."""
    for name in ("small.xplane.pb", "small_call.hlo.txt"):
        with gzip.open(DATA / f"{name}.gz", "rb") as src:
            (tmp_path / name).write_bytes(src.read())
    hlo = (tmp_path / "small_call.hlo.txt").read_text()
    t = devtrace.load(str(tmp_path / "small.xplane.pb"), [hlo])
    assert t.devices == [0]
    assert [s.name for s in t.spans] == ["bench.window", "bench.generate",
                                         "bench.call", "bench.summarize"]
    lo, hi = t.window
    assert 0 < t.busy_s()[0] <= hi - lo
    names = {op.name.split(".")[0] for op in t.ops}
    assert {"newton_residual_soa", "blockdiag_spmv_soa",
            "masked_update_wrms_soa", "block_inverse_soa"} <= names
    # a loop is never a leaf: its body's ops are (a conditional whose
    # branch ran no op is one, and the roofline leaves it out)
    assert not any(devtrace.split_instruction(op.text)[1] == "while"
                   for op in t.ops if not op.copy)
    assert any(op.copy for op in t.ops)
    assert {op.module for op in t.ops} == {"jit_call", "jit_inputs",
                                          "jit_summarize"}
    share, flagged = t.hbm_roofline(819e9)
    assert 0 < share < 100 and flagged == []
    assert 0 < len(t.top_ops()) <= 10 and 0 < len(t.top_gaps()) <= 10
