"""Driver-contract tests for ``__graft_entry__``: the driver imports the
module and calls ``dryrun_multichip(n)`` directly (it does NOT run the
``__main__`` block), so the function is exercised the same way here, on
the devices the process already has.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_dryrun_multichip_in_process():
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
        __graft_entry__.dryrun_multichip(8)
    finally:
        sys.path.remove(REPO)


def test_entry_returns_jittable():
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
        import jax
        fn, example_args = __graft_entry__.entry()
        out = jax.jit(fn).lower(*example_args)  # compile-check only
        assert out is not None
    finally:
        sys.path.remove(REPO)


def test_dryrun_multichip_raises_on_too_few_devices():
    """Too few devices is a clear error naming the count and the platform
    — never a teardown, a re-exec or a move to another platform."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
        import jax
        have = jax.device_count()
        with pytest.raises(RuntimeError,
                           match=rf"needs {have + 1} devices.*found {have}"
                                 r".*'cpu'"):
            __graft_entry__.dryrun_multichip(have + 1)
        assert jax.device_count() == have
        with open(os.path.join(REPO, "__graft_entry__.py")) as fh:
            src = fh.read()
        for gone in ("clear_backends", "subprocess", "jax_platforms"):
            assert gone not in src, gone
    finally:
        sys.path.remove(REPO)
