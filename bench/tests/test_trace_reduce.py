"""The trace-to-metric reduction, the kernel's byte count and the peaks
table, on hand-computed shapes and on a small trace recorded on the
CPU."""
import time

import pytest

from bench import peaks, trace_reduce as tr


def test_busy_is_the_union_of_overlapping_ops():
    ev = [("a", 10, 10), ("b", 15, 10), ("c", 40, 5), ("d", 90, 30)]
    assert tr.merged(ev) == [(10, 25), (40, 45), (90, 120)]
    # clipped to the slice [0, 100): 15 + 5 + 10
    assert tr.busy_ns(ev, 0, 100) == 30
    assert tr.idle_gaps(ev, 0, 100) == [(0, 10), (25, 40), (45, 90)]


def test_gap_takes_the_name_of_the_span_it_overlaps_most():
    spans = [("struct", 0, 100), ("struct.combine", 20, 30),
             ("stall.write", 60, 10)]
    assert tr.name_gap((25, 40), spans) == "struct.combine"
    assert tr.name_gap((55, 90), spans) == "struct"
    assert tr.name_gap((61, 69), spans) == "stall.write"
    assert tr.name_gap((200, 210), spans) == "no span"


KERNEL_OP = ('%tpu_custom_call.1 = (s32[8192]{0}, s32[8192]{0}) custom-call('
             's32[2]{0} %a, f32[18,4]{1,0} %b), custom_call_target='
             '"tpu_custom_call"')


def test_op_names_drop_the_hlo_text_and_numbering():
    assert tr.op_name(KERNEL_OP) == "tpu_custom_call"
    assert tr.op_name("%copy.12 = f32[18,4]{0,1} copy(f32[18,4] %x)") == \
        "copy"
    assert tr.op_name("%fusion = s32[7] fusion(s32[7] %x)") == "fusion"


def test_summary_of_a_slice():
    per_dev = {"/device:TPU:0": [(KERNEL_OP, 0, 20),
                                 ("%fusion.3 = s32[7] fusion()", 20, 10),
                                 (KERNEL_OP, 60, 20)]}
    s = tr.summarize(per_dev, 0, 100, [("struct.combine", 30, 30)])
    assert s.slice_ns == 100 and s.busy_ns == 50
    assert s.idle_share == pytest.approx(0.5)
    assert [n for n, _, _ in s.ops].count(KERNEL_OP) == 2
    assert s.device_ops == [("tpu_custom_call", 40e-9), ("fusion", 10e-9)]
    assert s.idle_gaps == [("struct.combine", 30e-9), ("no span", 20e-9)]


def test_a_slice_with_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize({"/device:TPU:0": [("x", 500, 10)]}, 0, 100)


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.anchor"):
        pass
    for _ in range(3):
        f(x).block_until_ready()
        time.sleep(0.005)
    jax.profiler.stop_trace()
    profile = jax.profiler.ProfileData.from_file(tr.find_xplane(
        str(tmp_path)))
    anchor = tr.host_events(profile, "bench.anchor")
    assert len(anchor) == 1
    ops = tr.device_events(profile, prefix="/host:CPU")
    assert sum(len(v) for v in ops.values()) >= 3
    lo = anchor[0][1]
    hi = max(s + d for v in ops.values() for _, s, d in v)
    s = tr.summarize(ops, lo, hi)
    assert 0 < s.busy_ns <= s.slice_ns
    # the three calls left the device idle between them
    assert 0 < s.idle_share < 1 and len(s.idle_gaps) >= 2


@pytest.mark.parametrize("text,want", [
    # two int32 id columns of 8192 edges, 2 seed words, 18 levels of θ
    (KERNEL_OP, 8 * 8192 + 8 + 16 * 18),
    # ids over 31 bits: a (hi, lo) pair of words per side
    ('%tpu_custom_call.7 = (u32[16384]{0}, u32[16384]{0}, u32[16384]{0}, '
     'u32[16384]{0}) custom-call(u32[2,2]{1,0} %s, f32[40,4]{1,0} %t), '
     'custom_call_target="tpu_custom_call", backend_config="s32[999]"',
     16 * 16384 + 16 + 16 * 40),
    # TPU layouts with tiles, and a metadata attribute naming a shape
    ('%tpu_custom_call = (s32[4096]{0:T(1024)}, s32[4096]{0:T(1024)}) '
     'custom-call(s32[1,2]{1,0:T(1,128)} %p), custom_call_target='
     '"tpu_custom_call", metadata={op_name="f32[7]"}', 8 * 4096 + 8),
    ("%copy.3 = bf16[2,3]{1,0} copy(bf16[2,3]{0,1} %x)", 24),
    ("%constant = pred[] constant(true)", 1),
    ("tpu_custom_call", 0),
])
def test_kernel_bytes_from_the_hlo_text(text, want):
    assert tr.hlo_bytes(text) == want


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def _ctx(**kw):
    from bench.cell import Context
    base = dict(window_s=10.0, spans={"struct": 9.0, "stall.write": 0.5,
                                      "write": 2.0},
                counters={"writer.bytes_flushed": 4e9},
                compiles_in_window=3, trace=None,
                peaks=peaks.peaks("TPU v5 lite"))
    base.update(kw)
    return Context(**base)


def test_readers_on_hand_made_readings():
    from bench import manifest
    read = {m: manifest._load_reader(m) for m in (
        "stall_pct", "struct_busy_pct", "compiles_in_window", "write_GBps",
        "device_idle_pct", "rmat_kernel_roofline")}
    ctx = _ctx()
    assert read["stall_pct"](ctx) == pytest.approx(5.0)
    assert read["struct_busy_pct"](ctx) == pytest.approx(90.0)
    assert read["compiles_in_window"](ctx) == 3.0
    assert read["write_GBps"](ctx) == pytest.approx(2.0)
    # nothing traced: the trace's readers read nothing
    assert read["device_idle_pct"](ctx) is None
    assert read["rmat_kernel_roofline"](ctx) is None

    least_s = 2 * tr.hlo_bytes(KERNEL_OP) / 819e9
    # the two calls took 4x their least time on the device
    ops = [(KERNEL_OP, 0, least_s * 1e9), (KERNEL_OP, 1e6, least_s * 3e9),
           ("%copy.1 = f32[2] copy()", 2e6, 1e3)]
    s = tr.summarize({"/device:TPU:0": ops}, 0, 1e7)
    ctx = _ctx(trace=s)
    assert read["rmat_kernel_roofline"](ctx) == pytest.approx(25.0)
    assert read["device_idle_pct"](ctx) == pytest.approx(
        100 * (1 - (4 * least_s * 1e9 + 1e3) / 1e7))
    # a slice in which the kernel did not run reads nothing
    other = tr.summarize({"/device:TPU:0": ops[2:]}, 0, 1e7)
    assert read["rmat_kernel_roofline"](_ctx(trace=other)) is None
