"""The trace reduction: interval arithmetic on hand-made inputs, the summary
on hand-made planes, and the whole path on a small trace recorded on a v5e
(``benchmark/tools/record_trace.py``)."""

import glob
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_total_clip():
    merged = trace.union([[3, 4], [0, 1], [0.5, 2], [2, 2.5]])
    assert merged == [[0, 2.5], [3, 4]]
    assert trace.total(merged) == 3.5
    assert trace.clip(merged, 1, 3.5) == [[1, 2.5], [3, 3.5]]


def test_subtract_and_overlap():
    assert trace.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == [[0, 1], [2, 4], [6, 9]]
    assert trace.subtract([[0, 1]], []) == [[0, 1]]
    assert trace.overlap([[0, 2], [5, 7]], [[1, 6]]) == 2
    assert trace.overlap([[0, 1]], [[1, 2]]) == 0


def planes():
    ops = [("while.9", 0.0, 2.5), ("fusion.1", 0.0, 1.0), ("all-gather.2", 1.0, 2.0),
           ("fusion.3", 1.5, 2.5), ("all-reduce.4", 3.0, 3.5), ("fusion.5", 9.5, 11.0)]
    return {
        "devices": {0: {"ops": ops, "async": [("all-gather-start.7", 0.2, 1.2), ("copy-start", 0, 9)],
                        "modules": [("jit_step_fn(123)", 0.0, 3.5),
                                    ("jit_step_fn(123)", 9.5, 11.0)]}},
        "host": [(trace.WINDOW_SPAN, 0.0, 10.0), ("bench.wait_loss", 2.4, 3.1),
                 ("bench.dispatch", 3.5, 4.0)],
    }


def test_reduce_on_hand_made_planes():
    s = trace.reduce(planes())
    assert s["window_s"] == 10.0 and s["devices"] == 1
    # busy: [0, 2.5] + [3, 3.5] + the part of the last op inside the window
    assert s["busy_s"] == pytest.approx(2.5 + 0.5 + 0.5)
    assert s["collective_s"] == pytest.approx(1.5)
    # all-gather is exposed for 0.5 s before fusion.3 starts; all-reduce wholly;
    # the while that contains them all hides nothing
    assert s["collective_exposed_s"] == pytest.approx(1.0)
    assert s["collective_async_s"] == pytest.approx(1.0)
    assert "while.9" not in [name for name, _, _ in s["ops"]]
    # a module counts where it lies wholly inside the window
    assert s["modules"] == {"jit_step_fn": {"count": 1, "total_s": 3.5}}
    gaps = dict(s["idle_gaps"])
    assert gaps["wait_loss"] == pytest.approx(0.5)  # idle [2.5, 3] under the wait
    assert gaps["dispatch"] == pytest.approx(0.5)
    assert gaps["host_unattributed"] == pytest.approx(6.5 - 0.5 - 0.5)
    assert s["ops"][0][0] in ("fusion.1", "fusion.3", "all-gather.2")
    b = trace.breakdown(s)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []})


def test_module_name():
    assert trace.op_name("%fusion.1 = bf16[512,512]{1,0:T(8,128)(2,1)} fusion(%x), kind=kOutput") == "fusion.1"
    assert trace.op_name("fusion.2") == "fusion.2"
    assert trace.CONTROL_FLOW.match("while.14") and not trace.CONTROL_FLOW.match("while_fusion")
    assert trace.module_name("jit_step_fn(1234567890)") == "jit_step_fn"
    assert trace.module_name("jit_decode_fn") == "jit_decode_fn"


def test_compile_counter_counts_a_new_program():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones((3,)))
    with trace.CompileCounter() as none:
        f(jnp.ones((3,)))
    with trace.CompileCounter() as one:
        f(jnp.ones((5,)))
    assert none.count == 0 and one.count >= 1


def test_recorded_trace():
    found = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    assert found, "the recorded trace is missing from benchmark/tests/data"
    s = trace.reduce(trace.read_planes(found[0]))
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    step = s["modules"]["jit_small_step"]
    # four executions were recorded; the device's clock reads about a
    # millisecond early, so the first may fall before the host's window
    assert step["count"] in (3, 4) and 0 < step["total_s"] <= s["busy_s"] * 1.01
    assert all("=" not in name and not name.startswith("%") for name, _, _ in s["ops"])
    gaps = dict(s["idle_gaps"])
    assert gaps.get("pause", 0) > 0  # the sleeps between steps show as idle
    assert {"dispatch", "wait_loss", "pause"} <= set(s["host_spans"])
    assert s["host_spans"]["pause"]["count"] == 4
