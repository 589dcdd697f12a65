"""The step wrapper's two stamps, on the CPU with a fake step: ``returns``
where the step call comes back, ``finishes`` where the step's work ends,
taken by the watcher thread from each step's loss in the order of the
calls. The fake's work ends when the test says so (an event), and every
assertion is an order of stamps or a wait the test itself made: no
threshold in milliseconds that a loaded machine could miss."""

import threading
import time

from lib.program import StepProbe

PATIENCE = 60.0  # seconds before a wait that should end gives the test up


class Loss:
    """Stands for a step's loss on the device: ready once ``finish()`` is
    called."""

    def __init__(self):
        self._done = threading.Event()
        self.finished_at = None
        self.waited_on = None

    def finish(self):
        self.finished_at = time.perf_counter()
        self._done.set()

    def block_until_ready(self):
        self.waited_on = threading.current_thread().name
        assert self._done.wait(PATIENCE), "the fake step's work was never ended"
        return self


def drive(probe: StepProbe, calls: int) -> list:
    """``calls`` step calls back to back, the way the loop calls its step;
    none of the steps' work has ended when this returns."""
    losses = []

    def step(state, batch):
        losses.append(Loss())
        return state + 1, {"loss": losses[-1]}

    probe.step = step
    state = 0
    for batch in range(calls):
        state, _ = probe(state, batch)
    assert state == calls
    return losses


def stamped(probe: StepProbe, count: int) -> None:
    """Wait until the watcher has stamped ``count`` finishes."""
    give_up = time.perf_counter() + PATIENCE
    while len(probe.finishes) < count:
        assert time.perf_counter() < give_up, f"{len(probe.finishes)} of {count} stamps came"
        time.sleep(0.001)


def test_as_many_finishes_as_returns_in_order():
    probe = StepProbe(None)
    losses = drive(probe, 8)            # every call came back with no step's work ended:
    assert probe.finishes == []         # the watcher never held the loop
    assert len(probe.returns) == len(probe.dispatch_s) == 8 and probe.losses == losses
    for loss in (losses[5], losses[2], losses[7]):
        loss.finish()                   # later steps end first: the stamps still follow the calls
    time.sleep(0.01)
    assert probe.finishes == []         # the first step's work has not ended
    for loss in losses:
        if loss.finished_at is None:
            loss.finish()
    probe.join()
    assert len(probe.finishes) == len(probe.returns) == 8
    assert probe.finishes == sorted(probe.finishes) and probe.returns == sorted(probe.returns)
    for ret, fin, loss in zip(probe.returns, probe.finishes, losses):
        assert fin >= ret and fin >= loss.finished_at  # stamped after the work ended, not before
        assert fin >= losses[0].finished_at            # and after every earlier step's
        assert loss.waited_on == "bench_finish"


def test_a_late_finish_shows_in_the_finish_intervals_only():
    """A step whose call returns at once and whose work ends 50 ms after its
    predecessor's: the 50 ms lie between two finish stamps and between no
    two return stamps."""
    probe = StepProbe(None)
    losses = drive(probe, 5)
    called = time.perf_counter()        # all five calls have returned
    losses[0].finish()
    losses[1].finish()
    stamped(probe, 2)
    time.sleep(0.05)                    # the third step's work takes 50 ms longer
    for loss in losses[2:]:
        loss.finish()
    probe.join()
    finish = [b - a for a, b in zip(probe.finishes, probe.finishes[1:])]
    assert len(finish) == 4 and finish[1] >= 0.05
    # every return stamp was taken before the first step's work ended, the 50 ms came after
    assert probe.returns[-1] <= called <= losses[0].finished_at <= probe.finishes[0]


def test_clear_between_set_up_and_window_leaves_nothing_behind():
    probe = StepProbe(None)
    losses = drive(probe, 3)            # set-up's steps, still in flight at clear()
    ender = threading.Timer(0.02, lambda: [loss.finish() for loss in losses])
    ender.start()
    probe.clear()                       # waits for them, then empties
    ender.join()
    assert all(loss.finished_at is not None for loss in losses)
    assert probe.finishes == probe.returns == probe.dispatch_s == probe.losses == []
    assert probe._watcher is None and probe._pending.empty()
    cleared = time.perf_counter()
    for loss in drive(probe, 2):
        loss.finish()
    probe.join()
    assert len(probe.finishes) == len(probe.returns) == 2
    assert probe.finishes[0] >= cleared  # no stamp of a set-up step among the window's
    probe.join()                         # a second join is a no-op
    assert len(probe.finishes) == 2
