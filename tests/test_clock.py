"""The clock every test runs under (tests/conftest.py)."""

import signal
import time

import pytest

from conftest import TEST_LIMIT_S, ClockExpired, clock


def test_outliving_the_clock_fails_by_name_and_clears_the_timer(request):
    t0 = time.monotonic()
    with pytest.raises(ClockExpired, match=request.node.name):
        with clock(request.node.nodeid, limit=0.2):
            time.sleep(30)
    assert time.monotonic() - t0 < 5
    # the test's own clock runs on, less what the inner one used
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert TEST_LIMIT_S - 5 < left < TEST_LIMIT_S


def test_the_next_test_starts_a_full_clock():
    """The timer a test leaves is its own: cleared at tear-down, set anew
    for the next one (the test above cannot reach into this one)."""
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert TEST_LIMIT_S - 5 < left <= TEST_LIMIT_S
    with clock("inner", limit=0.2):
        pass
    time.sleep(0.3)        # the inner timer, had it been left set
