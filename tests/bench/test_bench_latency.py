"""Latency runs from each request's due time, so a stall shows in every
request that fell due during it, not only in the one it hit."""
import numpy as np
import pytest

from bench import generator, harness


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class OneAtATime:
    """A server that answers one request per step of ``step_s``; the step
    at ``stall_at`` takes ``stall_s`` instead."""
    last_step_items = 0

    def __init__(self, clock, step_s=0.001, stall_at=0.5, stall_s=0.3):
        self.clock, self.step_s = clock, step_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.queue, self.t0 = [], clock()
        self.stalled = False

    def submit(self, i):
        self.queue.append(i)

    @property
    def active(self):
        return bool(self.queue)

    def step(self):
        now = self.clock() - self.t0
        if not self.stalled and now >= self.stall_at:
            self.stalled = True
            self.clock.sleep(self.stall_s)
        self.clock.sleep(self.step_s)
        i = self.queue.pop(0)
        self.last_step_items = 1
        return [(i, "done", i)]

    def admissions(self):
        return {}


def test_a_stall_inflates_every_request_due_during_it():
    clock = FakeClock()
    due = [0.1 * i for i in range(1, 10)]          # 0.1 .. 0.9 s
    sched = generator.Schedule("open", np.array(due))
    server = OneAtATime(clock)
    run = harness.drive_open(server, sched, 1.0, clock=clock,
                             sleep=clock.sleep)
    lat = {i: r.t_done - r.due for i, r in enumerate(run.reqs)}
    stall_end = 0.5 + 0.3
    for i, r in enumerate(run.reqs):
        if 0.5 <= r.due < stall_end:
            # waited out the rest of the stall, though submitted late
            assert lat[i] >= stall_end - r.due - 1e-9
            assert lat[i] > 0.05
        elif r.due < 0.5:
            assert lat[i] < 0.01
    # the generator's lag is reported, not hidden
    assert max(run.lag) >= 0.2
    assert all(r.t_done is not None for r in run.reqs)


class Streaming:
    """Answers each request in three parts, one per step."""
    last_step_items = 0

    def __init__(self, clock):
        self.clock, self.left = clock, {}

    def submit(self, i):
        self.left[i] = 3

    @property
    def active(self):
        return bool(self.left)

    def step(self):
        self.clock.sleep(0.01)
        events = []
        for i in list(self.left):
            self.left[i] -= 1
            events.append((i, "done" if self.left[i] == 0 else "out", i))
            if self.left[i] == 0:
                del self.left[i]
        self.last_step_items = len(events)
        return events

    def admissions(self):
        return {}


def test_streamed_parts_are_stamped_and_the_last_one_answers():
    clock = FakeClock()
    sched = generator.Schedule("open", np.array([0.1, 0.5]))
    run = harness.drive_open(Streaming(clock), sched, 1.0, clock=clock,
                             sleep=clock.sleep)
    for r in run.reqs:
        assert len(r.out_times) == 3
        assert r.out_times == sorted(r.out_times)
        assert r.t_done == r.out_times[-1]
        # three 10 ms steps from its due time (the sleep rounds up 1 us)
        assert r.t_done - r.due == pytest.approx(0.03, abs=1e-5)


def test_the_longest_stalls_are_reported_where_they_fell():
    clock = FakeClock()
    sched = generator.Schedule("open", np.array([0.1 * i
                                                 for i in range(1, 10)]))
    run = harness.drive_open(OneAtATime(clock), sched, 1.0, clock=clock,
                             sleep=clock.sleep)
    (when, lag), *_ = harness.stalls(run, sched)
    # the request due during the 0.3 s stall that waited longest to go in
    assert 0.5 <= when < 0.8 and lag > 0.2
    assert set(harness.host_counters()) >= {"cpu_s", "ctx_invol", "threads"}


def test_garbage_collections_are_timed_while_entered():
    import gc
    with harness.GcTimer() as g:
        gc.collect()
    assert g.n >= 1 and g.longest[0] == 2 and g.longest[1] > 0
    gc.collect()
    assert g.n >= 1 and g._on_gc not in gc.callbacks
