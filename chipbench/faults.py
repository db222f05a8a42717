"""Faults planted under the timed path, each of which ``correct`` must catch.

``FAULTS[driver name][fault name]`` is a ``patch(driver)`` applied after
set-up (``run.run_cell(..., patch=...)``): it breaks what the window's
calls produce, as a faulty program would.  The tests drive each on the
CPU; ``control.py --faults`` reads them on the chip at the cell's size.
In lane-sharded training the chips exchange nothing, so the fault of a
chip's share left out stands for a missing exchange there.
"""
from __future__ import annotations

import numpy as np


def _rewrite(fn):
    """A patch that rewrites every output of the driver's timed call."""
    def patch(driver):
        orig = driver.dispatch

        def dispatch(i):
            out = fn(orig(i))
            driver.outputs[-1] = out
            return out
        driver.dispatch = dispatch
    return patch


def _host(tree):
    import jax
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def _train_unchanged(qs):
    """Training returns the state it was given: the fresh table."""
    qs = _host(qs)
    return qs._replace(qtable=np.ones_like(qs.qtable),
                       visits=np.zeros_like(qs.visits),
                       step=np.zeros_like(qs.step))


def _half_batch(out):
    """Half of the agents (or policies) left out: their rows are copies of
    the first half's (the batch axis is axis 1 of every leaf)."""
    def half(x):
        n = x.shape[1]
        keep = x[:, : max(n // 2, 1)]
        return np.concatenate([keep] * -(-n // keep.shape[1]), axis=1)[:, :n]
    import jax
    return jax.tree_util.tree_map(half, _host(out))


def _chip_share_left_out(qs):
    """The last quarter of the agents (one chip's share on four) keeps its
    fresh table."""
    qs = _host(qs)
    q = qs.qtable.copy()
    q[:, q.shape[1] * 3 // 4:] = 1.0
    return qs._replace(qtable=q)


def _train_altered(qs):
    """One Q-value of one agent off where the kernel writes it."""
    qs = _host(qs)
    q = qs.qtable.copy()
    q[0, 0, 0, 0] += 0.01
    return qs._replace(qtable=q)


def _eval_altered(out):
    """One phase time of one fixed-mode episode off."""
    pt, po, mode = (x.copy() for x in _host(out))
    pt[0, 0, 0] *= 1.01
    return pt, po, mode


def _serve_unchanged(driver):
    """The carry is not carried: every chunk starts from idle queues."""
    orig = driver.dispatch

    def dispatch(i):
        driver.carry = driver.serve_env.init_carry(driver.spec.qstate)
        return orig(i)
    driver.dispatch = dispatch


def _serve_kept(edit):
    """A patch that edits each kept chunk's (executed, mode, latency)."""
    def patch(driver):
        orig = driver.pull

        def pull(i, out):
            orig(i, out)
            if len(driver.kept) == i + 1:
                driver.kept[-1] = edit(*(np.array(x)
                                         for x in driver.kept[-1]))
        driver.pull = pull
    return patch


def _serve_half(ex, mode, lat):
    """Half of each chunk's requests never decided (left as shed)."""
    ex[len(ex) // 2:] = False
    return ex, mode, lat


def _serve_altered(ex, mode, lat):
    """One admission decision flipped where the step makes it."""
    ex[0] = ~ex[0]
    return ex, mode, lat


FAULTS = {
    "train": {"state_unchanged": _rewrite(_train_unchanged),
              "half_batch": _rewrite(_half_batch),
              "chip_share_left_out": _rewrite(_chip_share_left_out),
              "answer_altered": _rewrite(_train_altered)},
    "eval": {"half_batch": _rewrite(_half_batch),
             "answer_altered": _rewrite(_eval_altered)},
    "serve": {"state_unchanged": _serve_unchanged,
              "half_batch": _serve_kept(_serve_half),
              "answer_altered": _serve_kept(_serve_altered)},
}
