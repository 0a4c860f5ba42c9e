"""``bench/trace_reduce.reduce_events`` on a hand-made trace whose busy
time, op times and idle gaps are counted by hand."""
import pytest

from bench import trace_reduce

# one device; the window runs from 0 to 10 s
EVENTS = {
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 3.0),   # 1..3
                ("dot.3", 5.0, 6.0),                              # 5..6
                ("fusion.1", 9.5, 11.0)],                         # 9.5..10
        "modules": [("jit_decode_step", 1.0, 3.0),
                    ("jit_decode_step", 5.0, 6.0)],
    }],
    "spans": [("window", 0.0, 10.0),
              ("orchestrator", 0.0, 4.0), ("collect", 0.6, 3.5),
              ("batcher.step", 0.8, 3.2),
              ("orchestrator", 4.5, 9.0), ("create_hook", 6.5, 8.0)],
}


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_events(EVENTS)


def test_busy_and_window(red):
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(2.0 + 1.0 + 0.5)
    assert red["devices"] == 1


def test_op_and_module_times(red):
    assert red["ops"] == pytest.approx({"fusion.1": 2.5, "fusion.2": 1.5,
                                        "dot.3": 1.0})
    assert red["modules"]["jit_decode_step"] == [2, pytest.approx(3.0)]
    assert red["breakdown"]["device_ops"][0] == ["fusion.1", 2.5]


def test_idle_gaps_by_host_span(red):
    # gaps: 0-1 (mid 0.5: orchestrator), 3-5 (mid 4.0: none), 6-9.5
    # (mid 7.75: create_hook)
    assert red["idle_by_host"] == pytest.approx(
        {"orchestrator": 1.0, "host_loop": 2.0, "create_hook": 3.5})
    assert red["breakdown"]["idle_gaps"][0] == ["create_hook", 3.5]
    assert sum(red["idle_by_host"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_no_device_or_no_window_gives_nothing():
    assert trace_reduce.reduce_events({"devices": [], "spans":
                                       EVENTS["spans"]}) is None
    assert trace_reduce.reduce_events({"devices": EVENTS["devices"],
                                       "spans": []}) is None
