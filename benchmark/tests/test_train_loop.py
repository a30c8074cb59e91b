"""The timed loop's order of dispatch and await, with a step that only counts."""

import pytest

from benchmark.kinds import train_steps


class Loss:
    """Stands for a step's loss on the device: ``float`` of it is the await."""

    def __init__(self, log, i):
        self.log, self.i = log, i

    def __float__(self):
        self.log.append(("await", self.i))
        return 1.0 / self.i


@pytest.mark.parametrize("in_flight", [1, 3, 10])
def test_the_host_keeps_in_flight_steps_queued_behind_the_one_it_awaits(in_flight):
    log = []

    def step(state, batch):
        log.append(("dispatch", state + 1))
        return state + 1, {"loss": Loss(log, state + 1)}

    warmup = 3
    res = train_steps.timed_loop(step, 0, None, warmup, 0.0, in_flight)
    dispatched = 0
    for what, i in log:
        if what == "dispatch":
            dispatched = i
        elif i <= warmup + 1:
            # whenever the host waits, in_flight steps are queued behind
            assert dispatched == i + in_flight
    awaited = [i for what, i in log if what == "await"]
    assert awaited == list(range(1, warmup + 2 + in_flight))  # in order, none lost
    assert len(res["warm_losses"]) == warmup
    # a window of no length holds one whole step; the queue is drained, uncounted
    assert len(res["done"]) == 1 and len(res["losses"]) == 1 + in_flight
    assert res["done"][0] >= res["t0"] and res["state"] == warmup + 1 + in_flight
