"""Not collected by itself (no `test_` in its name): `tests/test_suite_seams.py` hands it to a pytest of its own
to see `conftest.py`'s limit a test at work, with the limit cut to a second."""

import time

import conftest

conftest.TEST_LIMIT_S = 1.0


def test_sleeps_past_the_limit():
    time.sleep(60)


def test_the_one_behind_it_runs():
    pass
