"""The rest of a run, with the harness's look for a chip skipped: tiny
configurations served on the CPU through the real session, window and
output check. A sound program comes out correct; one whose answers are
altered where they are produced comes out not correct."""
import math

import jax
import numpy as np
import pytest

from bench import session
from bench.tests import helpers

SEED = 2 ** 31 + 5


def _phase(name):
    pass


def _serve(cell, devices=None, alter=False, seconds=0.5):
    if alter:
        program = cell.model.program

        def broken(spec, params, state, devs):
            ladder, fns = program(spec, params, state, devs)
            # one answer of every flush altered where the step produces it
            return ladder, [lambda x, f=f: f(x).at[0, 0].add(0.05)
                            for f in fns]

        cell.model = helpers.cells.model(cell.spec["model"])  # a fresh copy
        cell.model.program = broken
    devices = devices or jax.devices()[:1]
    sess = session.Session(cell, devices, _phase)
    win = sess.measure(SEED, seconds, _phase)
    return sess, win


@pytest.mark.parametrize("model,mix", [("darknet", helpers.TINY_CLOSED),
                                       ("kws", helpers.TINY_OPEN)])
@pytest.mark.parametrize("alter", [False, True])
def test_window_and_check(model, mix, alter):
    cell = helpers.tiny_cell(model, mix)
    cell.spec["limits"]["logit_gap"] = 1e-4
    sess, win = _serve(cell, alter=alter)
    assert win.attempted > 0 and win.failed == 0
    assert win.compiles == 0
    assert len(win.answers) == win.attempted
    assert np.all(np.isfinite(win.latency_ms))
    assert win.counters["served"] > 0
    assert set(win.flush_slots) <= {1, 2, 4, 8}
    correct, checks = session.check(cell, sess.params, sess.state, win)
    assert checks["failed"]["value"] == 0
    if alter:
        assert not correct and checks["logit_gap"]["value"] > 1e-3
    else:
        assert correct and checks["logit_gap"]["value"] < 1e-5


def test_replica_lanes_share_the_load():
    """Two lanes (over one CPU device here): the path a four-chip cell
    takes, with a stack copy and a step per lane."""
    cell = helpers.tiny_cell("darknet", helpers.TINY_CLOSED, chips=2)
    dev = jax.devices()[0]
    sess, win = _serve(cell, devices=[dev, dev])
    lanes = sess.batcher.stats["replicas"]
    assert len(lanes) == 2 and all(l["flushes"] > 0 for l in lanes)
    correct, _ = session.check(cell, sess.params, sess.state, win)
    assert correct


def test_missing_answer_is_not_correct():
    cell = helpers.tiny_cell("kws", helpers.TINY_OPEN)
    sess, win = _serve(cell, seconds=0.3)
    win.failed, win.answers = 1, win.answers[1:]
    win.latency_ms[0] = math.inf
    correct, checks = session.check(cell, sess.params, sess.state, win)
    assert not correct and checks["failed"]["value"] == 1
    assert session.percentile(win.latency_ms, 100) == math.inf


def test_percentile_is_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert session.percentile(v, 50) == 50
    assert session.percentile(v, 95) == 95
    assert session.percentile(np.array([1.0, math.inf]), 95) == math.inf


def test_session_refuses_another_edge_precision():
    """The program's float edges must be computed at the precision the
    configuration states, the one the reference uses."""
    cell = helpers.tiny_cell("kws", helpers.TINY_OPEN)
    cell.spec["edge_precision"] = "bf16x3"
    with pytest.raises(RuntimeError, match="edge"):
        session.Session(cell, jax.devices()[:1], _phase)
