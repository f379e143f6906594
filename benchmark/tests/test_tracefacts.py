"""The trace reduction against the small recorded trace: every expected
number below is worked out by hand from benchmark/recorded/trace_small.json."""
import json
import os

import pytest

from benchmark.harness import tracefacts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def raw():
    with open(os.path.join(BENCH, "recorded", "trace_small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def classes():
    return tracefacts.load_program_classes(BENCH)


def test_classes_from_files(classes):
    assert tracefacts.classify("jit__loop(102)", classes) == "decode"
    assert tracefacts.classify("jit__decode_block(7)", classes) == "decode"
    assert tracefacts.classify("jit__admit_many(101)", classes) == "prefill"
    assert tracefacts.classify("jit__extend_final(1)", classes) == "prefill"
    assert tracefacts.classify("jit__install_row(103)", classes) == "other"


def test_busy_union_and_idle(raw, classes):
    # window 0..20000 ns. Busy: 1000-3000, 4000-8000 (the while covers the
    # 100 ns its body leaves), 9000-9500, 12000-15000, 16000-17000 = 10500
    f = tracefacts.reduce(raw, classes, window=(0, 20000))
    assert f["chips"] == 1
    assert f["window_s"] == pytest.approx(20000e-9)
    assert f["busy_s"] == pytest.approx(10500e-9)
    assert f["idle_share"] == pytest.approx(1 - 10500 / 20000)


def test_default_window_is_the_events_span(raw, classes):
    f = tracefacts.reduce(raw, classes)
    assert f["window_s"] == pytest.approx(16000e-9)      # 1000..17000
    assert f["busy_s"] == pytest.approx(10500e-9)


def test_window_on_another_clock_falls_back(raw, classes):
    f = tracefacts.reduce(raw, classes, window=(10**12, 10**12 + 5000))
    assert f["window_s"] == pytest.approx(16000e-9)


def test_module_time_by_class(raw, classes):
    f = tracefacts.reduce(raw, classes, window=(0, 20000))
    assert f["class_s"]["decode"] == pytest.approx(7000e-9)
    assert f["class_s"]["prefill"] == pytest.approx(3000e-9)
    assert f["class_s"]["other"] == pytest.approx(500e-9)
    assert f["class_runs"] == {"prefill": 2, "decode": 2, "other": 1}
    # clipped: a window that ends inside the second loop
    g = tracefacts.reduce(raw, classes, window=(0, 13000))
    assert g["class_s"]["decode"] == pytest.approx(5000e-9)


def test_ops_count_their_own_time_grouped_by_module(raw, classes):
    f = tracefacts.reduce(raw, classes, window=(0, 20000))
    ops = dict((k, v) for k, v in f["device_ops"])
    assert ops["jit__loop/fusion.4"] == pytest.approx(3500e-9)
    assert ops["jit__loop/ragged_decode_q8.9 custom-call bf16[32,8,4,128]"] == pytest.approx(3400e-9)
    # the while holds its body: 7000 in all, 6900 of them its children's
    assert ops["jit__loop/while.3 while s32[]"] == pytest.approx(100e-9)
    assert ops["jit__admit_many/fusion.1"] == pytest.approx(1500e-9)
    assert f["device_ops"][0][0] == "jit__loop/fusion.4"
    assert len(f["device_ops"]) <= 10


def test_gap_labels(raw, classes):
    # in flight 500..18000, somebody decoding 3500..15500
    f = tracefacts.reduce(raw, classes, window=(0, 20000),
                          in_flight=[[500, 18000]], decoding=[[3500, 15500]])
    gaps = f["idle_gaps"]
    assert gaps[0] == ["no-request-in-flight", pytest.approx(3000e-9)]   # 17000-20000
    assert gaps[1] == ["decoding", pytest.approx(2500e-9)]               # 9500-12000
    labels = f["idle_s_by_label"]
    # 0-1000 (mid 500 is in flight, nobody decoding), 3000-4000 (mid 3500
    # decoding), 8000-9000, 9500-12000, 15000-16000 (mid 15500: not decoding)
    assert labels["no-request-in-flight"] == pytest.approx(3000e-9)
    assert labels["decoding"] == pytest.approx((1000 + 1000 + 2500) * 1e-9)
    assert labels["requests-queued-none-decoding"] == pytest.approx(2000e-9)
    # idle share over the in-flight span only: 17500 in flight, busy inside 10500
    assert f["idle_share_in_flight"] == pytest.approx(1 - 10500 / 17500)
    assert f["in_flight_s"] == pytest.approx(17500e-9)


def test_no_device_plane_gives_nothing(classes):
    assert tracefacts.reduce({"planes": [{"name": "/host:CPU", "lines": []}]},
                             classes) == {}


def test_steps_are_counted_from_the_trace(raw, classes):
    markers = tracefacts.load_step_markers(BENCH)
    assert "decode" in markers
    # three attention-kernel events in the two loop runs; with one layer
    # that is three steps, with three layers one
    f = tracefacts.reduce(raw, classes, window=(0, 20000), markers=markers,
                          config={"num_hidden_layers": 1})
    assert f["class_steps"] == {"decode": pytest.approx(3.0)}
    g = tracefacts.reduce(raw, classes, window=(0, 20000), markers=markers,
                          config={"num_hidden_layers": 3})
    assert g["class_steps"]["decode"] == pytest.approx(1.0)
    # an event that began before the window is not a step of it
    h = tracefacts.reduce(raw, classes, window=(5500, 20000), markers=markers,
                          config={"num_hidden_layers": 1})
    assert h["class_steps"]["decode"] == pytest.approx(2.0)


def test_op_names_are_shortened():
    assert tracefacts.op_short(
        "%copy.175 = s8[32,32,8,1536,128]{4,3,2,1,0:T(8,128)(4,1)} copy("
        "s8[32,32,8,1536,128]{4,3,2,1,0:T(8,128)(4,1)} %get-tuple-element.2649)"
    ) == "copy.175 copy s8[32,32,8,1536,128]"
    assert tracefacts.op_short(
        "%fusion.241 = (s32[32]{0:T(128)}, s32[32]{0:T(128)S(1)}) fusion("
        "fusion.239), kind=kLoop") == "fusion.241 fusion s32[32]"
    assert tracefacts.op_short("fusion.4") == "fusion.4"


# ------------------------- the head of a real TPU v5e trace (recorded/)

@pytest.fixture(scope="module")
def real():
    with open(os.path.join(BENCH, "recorded", "trace_v5e_head.json")) as f:
        return json.load(f)


def test_real_trace_modules_are_classified(real, classes):
    mods = [m[0] for m in real["planes"][0]["lines"][0]["events"]]
    assert [tracefacts.classify(m, classes) for m in mods] == [
        "prefill", "prefill", "decode"]


def test_real_trace_self_times_partition_the_busy_time(real, classes):
    # a while loop and its body are events of one line: if every op counts
    # only its own time, the ops' times add up to the busy union exactly
    f = tracefacts.reduce(real, classes, top=100000,
                          markers=tracefacts.load_step_markers(BENCH),
                          config={"num_hidden_layers": 6})
    assert sum(v for _, v in f["device_ops"]) == pytest.approx(f["busy_s"])
    assert 0 < f["busy_s"] < f["window_s"]
    assert sum(f["class_s"].values()) <= f["window_s"]
    names = [k for k, _ in f["device_ops"]]
    assert all(len(k) < 140 for k in names)
    assert any(k.startswith("jit__loop/ragged_decode_q8") for k in names)
    assert any(k.startswith("jit__admit_many/") for k in names)
    # the attention kernel is the step marker: it is found under its real name
    assert f["class_steps"]["decode"] > 0
