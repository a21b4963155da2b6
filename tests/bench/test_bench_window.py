"""The measured window closes at a step boundary, after the last step's
outputs are finished."""

import benchutil  # noqa: F401  (puts the benchmark on sys.path)
import pytest
from chipbench import harness


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("step_s, seconds, steps", [
    (0.3, 1.0, 4),      # 0.9 s is short of the window; the 4th step ends it
    (0.25, 1.0, 4),     # a step that ends exactly at the window's length
    (2.0, 1.0, 1),      # one step longer than the window still counts
    (0.1, 0.0, 1),      # a zero-length window runs one whole step
])
def test_window_closes_on_step_boundary(step_s, seconds, steps):
    clock = Clock()
    done = []

    def step(i):
        clock.t += step_s
        return i

    def finish(out):
        clock.t += 0.05           # the device finishing the last step
        done.append(out)

    n, t0, t1 = harness.timed_window(step, seconds, finish, clock)
    assert n == steps
    assert done == [steps - 1]                     # the last step's outputs
    assert t0 == pytest.approx(100.0)
    # the window spans its whole steps and the finish of the last
    assert t1 - t0 == pytest.approx(steps * step_s + 0.05)

