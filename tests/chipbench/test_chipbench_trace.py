"""The trace reduction, on a hand-made trace with known answers and on a
trace recorded on a TPU v5e and kept under ``chipbench/traces``."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import trace  # noqa: E402

KERNELS = {"cb_coo_spmv_batched"}


def hand_made():
    ms = 1e6  # ns
    return {
        "device": {
            "/device:TPU:0": [
                ["gather.3", "xla", 0 * ms, 1 * ms],
                ["cb_coo_spmv_batched", "pallas", 1 * ms, 6 * ms],
                ["scatter-add.fusion.1", "xla", 7 * ms, 1 * ms],
                ["reduce-scatter.2", "collective", 8 * ms, 0.5 * ms],
                ["gather.3", "xla", 10 * ms, 1 * ms],
                ["cb_coo_spmv_batched", "pallas", 11 * ms, 6 * ms],
            ],
            "/device:TPU:1": [
                # a loop holding a kernel and a collective: only its self
                # time (0.5 ms) counts as xla
                ["while.2 while", "xla", 0 * ms, 6.5 * ms],
                ["cb_coo_spmv_batched.1", "pallas", 0 * ms, 4 * ms],
                ["all-reduce.7", "collective", 4 * ms, 2 * ms],
            ],
        },
        "host": [
            ["dispatch", -1 * ms, 1 * ms],
            ["wait", 0 * ms, 8.6 * ms],
            ["dispatch", 8.6 * ms, 1.4 * ms],
            ["wait", 10 * ms, 8 * ms],
        ],
    }


def test_hand_made_trace():
    r = trace.reduce(hand_made())
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((15.5e-3 + 6.5e-3) / 2)
    assert r["class_s"]["pallas"] == pytest.approx((12e-3 + 4e-3) / 2)
    assert r["class_s"]["collective"] == pytest.approx((0.5e-3 + 2e-3) / 2)
    assert r["class_s"]["xla"] == pytest.approx((3e-3 + 0.5e-3) / 2)
    assert r["device_ops"][0] == ["cb_coo_spmv_batched", pytest.approx(6e-3)]
    # gaps on the first chip: 8.5-10 ms (host dispatching), 17-18 ms
    # (waiting), and -1-0 ms before the first op (dispatching)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["dispatch", pytest.approx(1.5e-3)]
    assert sorted(g[1] for g in gaps) == pytest.approx([1e-3, 1e-3, 1.5e-3])


# Event names as a TPU v5e trace gives them (HLO instruction text).
TEXTS = [
    ('%cb_coo_spmv_batched.1 = f32[60090,36,16]{2,1,0:T(8,128)} custom-call('
     's32[60090,1,288]{2,1,0:T(1,128)} %copy.10), custom_call_target='
     '"tpu_custom_call", operand_layout_constraints={s32[60090,1,288]{2,1,0}}',
     ("cb_coo_spmv_batched.1 custom-call", "pallas")),
    ('%fusion = f32[17305920]{0:T(1024)S(1)} fusion(f32[524288]{0:T(1024)S(1)}'
     ' %copy-done, s32[17306624]{0:T(1024)} %pad_clamp_fusion), kind=kCustom',
     ("fusion", "xla")),
    ('%slice-start.4 = ((s32[60090,288]{0,1:T(8,128)}), s32[60090,72]'
     '{0,1:T(8,128)S(1)}, s32[]{:S(2)}) async-start(...)',
     ("slice-start.4 async-start", "xla")),
    ('%reduce-scatter.3 = f32[131072]{0} reduce-scatter(f32[524288]{0} %y), '
     'replica_groups={{0,1,2,3}}, dimensions={0}',
     ("reduce-scatter.3", "collective")),
    ('%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %p)',
     ("all-reduce-start.1", "collective")),
    ('%all-gather-fusion.2 = f32[8]{0} fusion(f32[2]{0} %p), kind=kLoop',
     ("all-gather-fusion.2 fusion", "collective")),
    ('%custom-call.14 = s32[9537,32]{0,1:T(8,128)S(1)} custom-call(s32[9537,8])'
     ', custom_call_target="AllocateBuffer"',
     ("custom-call.14", "xla")),
]


@pytest.mark.parametrize("text,expected", TEXTS)
def test_event_names_and_classes(text, expected):
    assert trace.event(text, KERNELS) == expected


def test_a_named_kernel_is_pallas_without_the_custom_call_text():
    assert trace.event("cb_coo_spmv_batched.12", KERNELS)[1] == "pallas"


def test_empty_trace_reads_nothing():
    assert trace.reduce({"device": {}, "host": []}) is None
    assert trace.reduce({"device": {"/device:TPU:0": []}, "host": []}) is None


RECORDED = sorted((ROOT / "chipbench" / "traces").glob("*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_recorded_trace(path):
    rec = json.loads(path.read_text())
    r = trace.reduce(rec["trace"])
    exp = rec["expected"]
    assert r["chips"] == exp["chips"]
    assert r["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    for c, v in exp["class_s"].items():
        assert r["class_s"][c] == pytest.approx(v, rel=1e-9, abs=1e-12)
    assert r["class_s"]["pallas"] > 0
    assert r["busy_s"] <= rec["window_s"]
    # self times partition the busy time: nothing counted twice
    assert sum(r["class_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["device_ops"][0][1] <= r["busy_s"]
