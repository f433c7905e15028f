"""The trace reduction on small traces whose numbers are worked by hand,
and on a window recorded on a TPU v5e."""

from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def hand():
    ops0 = [("fusion.1", 0, 10), ("all-to-all.2", 10, 30), ("fusion.3", 20, 40),
            ("collective-permute-start.4", 50, 51), ("fusion.5", 52, 60),
            ("collective-permute-done.4", 70, 72)]
    # The layer scan's while loop spans 0..72 around its body's ops: it is
    # no work of its own and covers no collective.
    ops0.append(("while.9", 0, 72))
    return tr.Trace(
        window=(0, 100),
        devices={0: [tr.Op(*o) for o in sorted(ops0, key=lambda o: o[1])],
                 1: [tr.Op("fusion.1", 0, 50)]},
        spans=[("bench.window", 0, 100), ("bench.step", 0, 5), ("bench.fetch", 60, 100)],
        # Two runs of the step's program on device 0 (the second ends past
        # the window), one on device 1, and an eager program in between.
        modules={0: [tr.Op("jit_step", 0, 45), tr.Op("jit_argmax", 46, 47),
                     tr.Op("jit_step", 50, 110)],
                 1: [tr.Op("jit_step", 0, 50)]},
    )


def test_busy(hand):
    assert tr.busy_ns(hand, 0) == 40 + 1 + 8 + 2
    assert tr.busy_ns(hand, 1) == 50


def test_collectives(hand):
    # all-to-all 10..30; the async permute in flight from its start (50) to
    # the end of its done (72).
    assert tr.collective_intervals(hand.devices[0]) == [(10, 30), (50, 72)]
    assert tr.union_ns(tr.collective_intervals(hand.devices[0], ("all-to-all",)), hand.window) == 20
    # exposed: 10..20 of the all-to-all and 50..52, 60..72 of the permute.
    assert tr.exposed_ns(hand, 0) == 10 + 2 + 12
    assert tr.exposed_ns(hand, 1) == 0


def test_op_time_and_top_ops(hand):
    assert tr.op_ns(hand, 0, lambda o: o.name.startswith("fusion")) == (38, 3)
    assert "while" not in dict(tr.top_ops(hand))
    top = dict(tr.top_ops(hand))
    assert top["fusion"] == pytest.approx((38 + 50) / 2 * 1e-9)
    assert top["all-to-all"] == pytest.approx(10e-9)


def test_idle_gaps_named_by_host_span(hand):
    gaps = tr.idle_gaps(hand)
    assert gaps[0] == ["bench.fetch", pytest.approx(50e-9)]   # device 1, 50..100
    assert gaps[1] == ["bench.fetch", pytest.approx(28e-9)]   # device 0, 72..100
    assert sorted(g[1] for g in gaps[2:4]) == [pytest.approx(10e-9)] * 2
    assert ["idle", pytest.approx(10e-9)] in gaps             # 40..50: the host ran no span
    assert len(gaps) == 5


def test_pallas_operands():
    norm = ('%closed_call.16 = bf16[4,4096]{1,0} custom-call(bf16[4,4096]{1,0} %bitcast.185, '
            'bf16[4096]{0} %fusion.4), custom_call_target="tpu_custom_call", '
            'frontend_attributes={kernel_metadata={}}')
    attn = ('%closed_call.3 = bf16[64,1024,128]{2,1,0} custom-call(bf16[64,1024,128]{2,1,0} %a, '
            'bf16[16,1024,128]{2,1,0} %b, bf16[16,1024,128]{2,1,0} %c), '
            'custom_call_target="tpu_custom_call"')
    assert tr.op_name(norm) == "closed_call.16"
    assert tr.pallas_operands(tr.Op(tr.op_name(norm), 0, 1, norm)) == 2
    assert tr.pallas_operands(tr.Op(tr.op_name(attn), 0, 1, attn)) == 3
    assert tr.pallas_operands(tr.Op("fusion.1", 0, 1, "%fusion.1 = f32[] fusion()")) is None


def test_module_time(hand):
    assert tr.module_ns(hand, 0, "jit_step") == (45 + 50, 2)
    assert tr.module_ns(hand, 0, "jit_argmax") == (1, 1)
    assert tr.module_ns(hand, 1, "jit_step") == (50, 1)
    assert tr.module_ns(hand, 1, "jit_other") == (0, 0)
    assert tr.mean_module(hand, "jit_step") == ((95 + 50) / 2, 1.5)


def test_clipped_to_window(hand):
    hand.window = (5, 25)
    assert tr.busy_ns(hand, 0) == 20
    assert tr.busy_ns(hand, 1) == 20


def test_json_round_trip(hand, tmp_path):
    hand.write(tmp_path / "t.json.gz")
    back = tr.Trace.read(tmp_path / "t.json.gz")
    assert back.devices == hand.devices and back.spans == hand.spans
    assert back.modules == hand.modules


def test_recorded_chip_trace():
    """60 ms of the serve cell's window, recorded on a TPU v5e:
    decode steps, each waiting for its token's host round trip."""
    t = tr.Trace.read(DATA / "serve-chat-b4.json.gz")
    assert list(t.devices) == [0] and t.window_ns == pytest.approx(60e6)
    assert tr.busy_ns(t, 0) / t.window_ns == pytest.approx(0.8224, abs=1e-4)
    top = tr.top_ops(t, 3)
    assert top[0][0] == "fusion" and top[0][1] == pytest.approx(0.045258, rel=1e-4)
    assert "while" not in dict(tr.top_ops(t, 1000))
    gaps = tr.idle_gaps(t, 3)
    assert [g[0] for g in gaps] == ["bench.fetch"] * 3
    assert gaps[0][1] == pytest.approx(2.516931e-3, rel=1e-6)
    norms = [o for o in t.devices[0] if tr.pallas_operands(o) == 2]
    assert norms and not [o for o in t.devices[0] if tr.pallas_operands(o) == 3]
    assert tr.exposed_ns(t, 0) == 0  # one chip: no collectives


def test_step_readers_use_the_step_programs_device_time():
    """The model-step readers divide by the device time of the step's own
    program, not by the traced window (1 s here) nor by eager programs."""
    from bench import run

    root = Path(__file__).resolve().parents[2]
    t = tr.Trace(window=(0, 1e9), devices={0: []}, spans=[],
                 modules={0: [tr.Op("jit_decode_step", 0, 2e8), tr.Op("jit_argmax", 2e8, 3e8),
                              tr.Op("jit_decode_step", 4e8, 6e8)]})
    window = {"steps": 2, "step_module": "jit_decode_step",
              "step_bytes": [100e6, 300e6], "step_flops": [2e9, 6e9]}
    peaks = {"hbm_bytes_per_s": 2e9, "bf16_flops_per_s": 1e11}
    reading = run.Reading(None, t, window, peaks, 1)

    def read(metric):
        return run.load_module(root / "bench" / "metrics" / f"{metric}.py").read(reading)

    assert read("serve.decode_hbm_share") == pytest.approx(50.0)  # 400 MB in 0.4 s of 2 GB/s
    assert read("serve.decode_mfu") == pytest.approx(20.0)        # 8 GFLOP in 0.4 s of 100 TFLOP/s
    reading.window = {**window, "step_module": "jit_train_step", "step_flops": 2e9}
    assert read("train.mfu") is None                                # no run of that program
    reading.trace.modules[0].append(tr.Op("jit_train_step", 7e8, 8e8))
    assert read("train.mfu") == pytest.approx(20.0)                # 2 GFLOP in 0.1 s
