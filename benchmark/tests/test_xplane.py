"""The trace reduction on a small trace built with the plane, line and event
names the chip's profiler writes (``/device:TPU:0`` with ``XLA Ops`` and
``XLA Modules``, ``/host:CPU`` with the benchmark's spans): busy / idle, a
named kernel's time, launches per program, a gap's label."""
import pytest

from benchmark.lib import xplane

QKV = "bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}"
KERNEL = (f'%jvp_jit__unknown___.24 = ({QKV}, f32[128,1,1024]{{2,1,0}}) '
          f'custom-call({QKV} %bitcast.1, {QKV} %bitcast.2, {QKV} %bitcast.3), '
          'custom_call_target=\\"tpu_custom_call\\", '
          f'operand_layout_constraints={{{QKV}, {QKV}, {QKV}}}')
# another Pallas kernel of the step, handed other shapes: not attention
OTHER = ('%fused_update.2 = bf16[1024,4096]{1,0} custom-call(bf16[1024,4096]{1,0}'
         ' %p.7, bf16[1024,4096]{1,0} %p.8), '
         'custom_call_target=\\"tpu_custom_call\\"')
FUSION = "%fusion.12 = bf16[8,1024]{1,0} fusion(bf16[8,1024]{1,0} %p.1)"
COPY = "%copy.3 = bf16[16,64]{1,0} copy(bf16[16,64]{0,1} %p.2)"

# times in picoseconds from the line's start (1 us = 1e6 ps)
TRACE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  event_metadata {{ key: 1 value {{ id: 1 name: "{KERNEL}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{FUSION}" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{COPY}" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit_step_fn(123)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit__threefry_fold_in(9)" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "{OTHER}" }} }}
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 300000000 }}
    events {{ metadata_id: 2 offset_ps: 300000000 duration_ps: 50000000 }}
    events {{ metadata_id: 6 offset_ps: 350000000 duration_ps: 50000000 }}
    events {{ metadata_id: 3 offset_ps: 600000000 duration_ps: 100000000 }}
    events {{ metadata_id: 1 offset_ps: 800000000 duration_ps: 200000000 }}
  }}
  lines {{
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 4 offset_ps: 0 duration_ps: 400000000 }}
    events {{ metadata_id: 5 offset_ps: 600000000 duration_ps: 100000000 }}
    events {{ metadata_id: 4 offset_ps: 800000000 duration_ps: 200000000 }}
  }}
  lines {{
    id: 3 name: "Async XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: 1000000000 }}
  }}
}}
planes {{
  id: 2 name: "/host:CPU"
  event_metadata {{ key: 1 value {{ id: 1 name: "make_batch" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "train_step" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "unrelated" }} }}
  lines {{
    id: 1 name: "python3" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 390000000 duration_ps: 200000000 }}
    events {{ metadata_id: 2 offset_ps: 700000000 duration_ps: 90000000 }}
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: 1000000000 }}
  }}
}}
"""


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    return xplane.reduce_file(str(path), ("make_batch", "train_step"))


def test_busy_and_window(reduced):
    # ops cover [0,400] [600,700] [800,1000] us; the async line does not count
    assert reduced["chips"] == 1
    assert reduced["busy_s"] == pytest.approx(700e-6)
    assert reduced["window_s"] == pytest.approx(1000e-6)


def test_kernel_time_by_what_the_kernel_is_handed(reduced):
    qkv = "[128,1024,64]"
    assert xplane.kernel_time(reduced, [qkv] * 3) == pytest.approx(500e-6)
    assert xplane.kernel_time(reduced, [qkv] * 4) == 0  # it takes three
    assert xplane.kernel_time(reduced, ["[1024,4096]"]) == pytest.approx(50e-6)
    assert xplane.kernel_time(reduced, ["[8,1024]"]) == 0  # a fusion's shape
    assert {k["name"] for k in reduced["kernels"]} == {
        "jvp_jit__unknown___", "fused_update"}
    assert reduced["op_seconds"]["fusion"] == pytest.approx(50e-6)


def test_attention_roofline_counts_only_kernels_handed_q_k_v(reduced):
    from benchmark.metrics import train_flash_attn_roofline as metric

    sizes = {"n_layer": 1, "n_embd": 1024, "n_head": 16}
    record = {"trace": reduced, "window": {"traced_steps": 1}, "sizes": sizes,
              "traffic": {"batch": 8, "seq": 1024},
              "device": {"kind": "TPU v5 lite"}}
    # forward 4*8*1024*1024*1025/2 FLOPs, backward twice that, at 197 TFLOP/s
    least = 3 * 4 * 8 * 1024 * 1024 * 1025 / 2 / 197e12
    assert metric.read(record) == pytest.approx(100 * least / 500e-6)
    other = dict(record, traffic={"batch": 4, "seq": 1024})
    assert metric.read(other) is None  # no kernel of that cell's shape


def test_launches_and_stable_names(reduced):
    assert reduced["launches"] == {"jit_step_fn": 2,
                                   "jit__threefry_fold_in": 1}
    assert xplane.stable_name(FUSION.replace('\\"', '"')) == "fusion"


def test_gaps_are_labelled_by_the_span_that_covers_them(reduced):
    # gap [400,600] is mostly make_batch (390-590); gap [700,800] train_step
    assert reduced["gap_seconds"] == pytest.approx(
        {"make_batch": 200e-6, "train_step": 100e-6})
    b = xplane.breakdown(reduced)
    assert b["device_ops"][0][0] == "jvp_jit__unknown___"
    assert b["idle_gaps"][0] == ["make_batch", pytest.approx(200e-6)]


def test_no_device_plane_reads_nothing(tmp_path):
    from jax.profiler import ProfileData

    host_only = TRACE[TRACE.index('planes {\n  id: 2'):]
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(host_only))
    assert xplane.reduce_file(str(path)) is None
