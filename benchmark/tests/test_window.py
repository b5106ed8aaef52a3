"""The window rule: never zero jobs, no start that cannot fit."""

from harness.cell import run_window


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _jobs(clock, walls):
    def run_one(i):
        clock.now += walls[i]
        return {"wall_s": walls[i]}
    return run_one


def test_at_least_one_job_runs_when_seconds_is_shorter_than_a_job():
    clock = Clock()
    jobs = run_window(10.0, _jobs(clock, [27.0, 27.0]), clock)
    assert len(jobs) == 1


def test_a_job_that_cannot_fit_is_not_started():
    clock = Clock()
    # 30 s window, 14 s jobs: after two, 2 s are left
    jobs = run_window(30.0, _jobs(clock, [14.0] * 5), clock)
    assert len(jobs) == 2
    # after one 27 s job 3 s are left: no second start
    clock = Clock()
    assert len(run_window(30.0, _jobs(clock, [27.0] * 5), clock)) == 1


def test_the_rule_uses_the_previous_jobs_wall_time():
    clock = Clock()
    # the second job was slow (20 s): 12 s left, so no third, although a
    # job like the first (8 s) would have fitted
    jobs = run_window(40.0, _jobs(clock, [8.0, 20.0, 8.0]), clock)
    assert len(jobs) == 2

