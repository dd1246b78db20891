"""Trace reduction on a constructed trace: busy union, idle gaps by host
span, kernel time by name, and which Pallas kernel a device op is."""
import dataclasses

import pytest

from bench.lib import kernels as K
from bench.lib import trace as TR

# operand text as the TPU profiler shows it (from a v5e trace of the
# stage-3 cell; layouts shortened)
DECODE = ('%closed_call.64 = bf16[16,32,1,64]{3,2,1,0} custom-call('
          's32[16]{0:T(128)S(1)} %copy-done.13, bf16[16,32,1,64]{3,2,1,0} '
          '%copy.78, bf16[16,32,512,64]{3,2,1,0} %copy-done.1, '
          'bf16[16,32,512,64]{3,2,1,0} %f.5), custom_call_target='
          '"tpu_custom_call", operand_layout_constraints={s32[16]{0}}')
FWD = ('%closed_call.3 = bf16[16,32,1,512,64] custom-call(bf16[16,32,1,512,64]'
       ' %a, bf16[16,32,512,64] %b, bf16[16,32,512,64] %c), '
       'custom_call_target="tpu_custom_call"')
BWD = ('%closed_call.9 = bf16[16,32,512,64] custom-call(bf16[16,32,1,512,64]'
       ' %a, bf16[16,32,1,512,64] %g, bf16[16,32,512,64] %b, '
       'bf16[16,32,512,64] %c, f32[16,32,1,512] %l, f32[16,32,1,512] %d), '
       'custom_call_target="tpu_custom_call"')
PAGED = ('%closed_call.2 = bf16[64,3,3,64] custom-call(s32[64,112] %t, '
         's32[64] %l, bf16[64,3,3,64] %q, bf16[4096,3,16,64] %k, '
         'bf16[4096,3,16,64] %v), custom_call_target="tpu_custom_call"')
# the decode kernels on the stacked arena and pool (lens and the layer
# index, or block tables, lens and the layer index, by scalar prefetch),
# in fp and with int8 caches and their scale planes
DECODE_STACKED = ('%closed_call.64 = bf16[16,32,1,64] custom-call(s32[16] %l, '
                  's32[1] %i, bf16[16,32,1,64] %q, bf16[3,16,32,512,64] %k, '
                  'bf16[3,16,32,512,64] %v), custom_call_target='
                  '"tpu_custom_call"')
DECODE_STACKED_INT8 = ('%closed_call.7 = bf16[16,32,1,64] custom-call(s32[16] '
                       '%l, s32[1] %i, bf16[16,32,1,64] %q, s8[3,16,32,512,64]'
                       ' %k, s8[3,16,32,512,64] %v, f32[3,16,32,512] %ks, '
                       'f32[3,16,32,512] %vs), custom_call_target='
                       '"tpu_custom_call"')
PAGED_STACKED = ('%closed_call.2 = bf16[64,3,3,64] custom-call(s32[64,112] %t,'
                 ' s32[64] %l, s32[1] %i, bf16[64,3,3,64] %q, '
                 'bf16[2,4096,3,16,64] %k, bf16[2,4096,3,16,64] %v), '
                 'custom_call_target="tpu_custom_call"')
# MLA at DeepSeek-V2-Lite's head sizes: q and k 128 + 64 wide, v 128
MLA_FWD = ('%closed_call.5 = bf16[4,16,1,512,128] custom-call('
           'bf16[4,16,1,512,192] %a, bf16[4,16,512,192] %b, '
           'bf16[4,16,512,128] %c), custom_call_target="tpu_custom_call"')
MLA_BWD = ('%closed_call.6 = bf16[4,16,512,192] custom-call('
           'bf16[4,16,1,512,192] %a, bf16[4,16,1,512,128] %g, '
           'bf16[4,16,512,192] %b, bf16[4,16,512,128] %c, '
           'f32[4,16,1,512] %l, f32[4,16,1,512] %d), '
           'custom_call_target="tpu_custom_call"')
NORM = ('%closed_call.1 = bf16[16,2048] custom-call(bf16[16,2048]{1,0} %x, '
        'bf16[2048]{0} %w), custom_call_target="tpu_custom_call"')
FUSION = '%fusion.2 = f32[8] fusion(f32[8] %p), kind=kLoop'


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: tuple = ()


def planes():
    ms = 1e6
    host = [("python", [Ev(TR.WINDOW_SPAN, 0, 100 * ms),
                        Ev("stage3.generate", 0, 50 * ms),
                        Ev("stage3.train", 52 * ms, 48 * ms)])]
    dev = [("XLA Modules", [Ev("jit_step", 0, 100 * ms)]),
           ("XLA Ops", [Ev("decode", 10 * ms, 20 * ms, (("long", DECODE),)),
                        Ev("decode", 20 * ms, 20 * ms, (("long", DECODE),)),
                        Ev("matmul", 60 * ms, 30 * ms),
                        Ev("late", 120 * ms, 5 * ms)])]
    return [("/host:CPU", host), ("/device:TPU:0", dev)]


def test_busy_union_and_window():
    s = TR.reduce_events(planes())
    assert s.window_s == pytest.approx(0.1)
    # [10, 40] and [60, 90] ms; the op after the window is left out
    assert s.busy_s == pytest.approx(0.06)
    assert s.n_devices == 1
    assert s.ops["decode"] == (2, pytest.approx(0.04))
    assert "late" not in s.ops


def test_idle_gaps_by_host_span():
    s = TR.reduce_events(planes())
    gaps = dict(s.idle_gaps)
    # holes 0-10 and 40-60 ms (midpoint 50, inside generate) and 90-100
    # ms (inside train)
    assert gaps["stage3.generate"] == pytest.approx(0.03)
    assert gaps["stage3.train"] == pytest.approx(0.01)
    assert TR.breakdown(s)["device_ops"][0][0] == "decode"


def test_no_window_span_is_an_error():
    p = planes()
    p[0] = ("/host:CPU", [("python", [Ev("x", 0, 1)])])
    with pytest.raises(ValueError):
        TR.reduce_events(p)


@pytest.mark.parametrize("detail,kind", [
    (DECODE, "dense_decode"), (FWD, "flash_fwd"), (BWD, "flash_bwd"),
    (PAGED, "paged_decode"), (NORM, "rmsnorm"), (FUSION, None),
    (DECODE_STACKED, "dense_decode"), (DECODE_STACKED_INT8, "dense_decode"),
    (PAGED_STACKED, "paged_decode"), (MLA_FWD, "flash_fwd"),
    (MLA_BWD, "flash_bwd")])
def test_kernel_classification(detail, kind):
    assert K.classify(K.operands(detail)) == kind


def test_flash_shape_from_operands():
    assert K.flash_shape(K.operands(FWD)) == (16, 512, 512, 32, 32, 64, 64)
    # the backward's second operand is dO, whose third axis is G: the key
    # length and the v width come from k and v
    assert K.flash_shape(K.operands(BWD)) == (16, 512, 512, 32, 32, 64, 64)


@pytest.mark.parametrize("detail", [MLA_FWD, MLA_BWD])
def test_flash_shape_where_v_is_narrower(detail):
    assert K.flash_shape(K.operands(detail)) == (4, 512, 512, 16, 16, 192,
                                                 128)
