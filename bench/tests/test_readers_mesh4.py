"""The mesh cell's readers: the struct stage's per-step spans and the mesh
step's roofline, on hand-made readings and a hand-made trace whose op
texts are those the TPU compiler gives the step."""
import pytest

from bench import manifest, peaks, trace_reduce as tr
from bench.cell import Context


def _ctx(spans=None, counters=None, trace=None):
    return Context(window_s=10.0, spans=spans or {}, counters=counters or {},
                   compiles_in_window=0, trace=trace,
                   peaks=peaks.peaks("TPU v5 lite"))


@pytest.mark.parametrize("metric,span", [
    ("mesh_step_ms", "struct.device_step"),
    ("mesh_dispatch_ms", "struct.dispatch"),
    ("mesh_fetch_ms", "struct.fetch"),
])
def test_span_per_step(metric, span):
    read = manifest._load_reader(metric)
    assert read(_ctx({span: 1.5}, {"struct.steps": 30.0})) \
        == pytest.approx(50.0)
    # a program that does not count its steps reads nothing
    assert read(_ctx({span: 1.5})) is None
    assert read(_ctx({span: 1.5}, {"struct.steps": 0.0})) is None
    assert read(_ctx({}, {"struct.steps": 30.0})) is None


#: the two ops of the compiled v5e step that write a chip's id columns
#: (2^22 edges each), and two of its small ops
SRC_OP = ("%shift-left_add_fusion = s32[4194304]{0:T(1024)} fusion(%xor.1433,"
          " %bitcast.133), kind=kLoop, calls=%fused_computation.2")
DST_OP = ("%add_add_fusion = s32[4194304]{0:T(1024)} fusion(%xor.1042, "
          "%bitcast.38), kind=kLoop, calls=%fused_computation.1")
KEYS_OP = ("%pad_add_fusion = u32[26,2]{0,1:T(2,128)S(1)} fusion("
           "%add_add_fusion.1, %broadcast_add_fusion), kind=kLoop")
SEED_OP = "%copy.4 = s32[1]{0:T(128)S(6)} copy(s32[1]{0} %param.6)"


def test_results_of_an_op():
    mod = _module()
    assert mod.results(SRC_OP) == [("s32", 4194304)]
    assert mod.results(KEYS_OP) == []
    assert mod.results(SEED_OP) == [("s32", 1)]
    # a kernel's tuple of id columns; operands and attributes do not count
    kernel = ('%tpu_custom_call = (s32[4096]{0:T(1024)}, s32[4096]{0:T(1024)'
              '}) custom-call(s32[1,2]{1,0:T(1,128)} %p), metadata={op_name='
              '"s32[9]"}')
    assert mod.results(kernel) == [("s32", 4096), ("s32", 4096)]
    assert mod.results("add_add_fusion") == []


def _module():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_metric_mesh_step_roofline",
        manifest.reader_path("mesh_step_roofline"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_mesh_step_roofline():
    read = manifest._load_reader("mesh_step_roofline")
    assert read(_ctx()) is None
    # one step on each of two chips: each chip writes 2 x 2^22 int32 ids,
    # the least time of which is 8 B x 4194304 / 819e9 s a chip; each
    # chip's ops take 10 times that
    least_ns = 8 * 4194304 / 819e9 * 1e9
    per_chip = [(KEYS_OP, 0, 0.5 * least_ns),
                (SEED_OP, 0.5 * least_ns, 0.5 * least_ns),
                (SRC_OP, least_ns, 4.5 * least_ns),
                (DST_OP, 5.5 * least_ns, 4.5 * least_ns)]
    s = tr.summarize({"/device:TPU:0": per_chip,
                      "/device:TPU:1": per_chip}, 0, 20 * least_ns)
    assert read(_ctx(trace=s)) == pytest.approx(10.0)
    # a slice with no integer result reads nothing
    keys = tr.summarize({"/device:TPU:0": per_chip[:1]}, 0, least_ns)
    assert read(_ctx(trace=keys)) is None
