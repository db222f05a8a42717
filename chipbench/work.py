"""The algorithmic work of one simulated invocation, from the step's shapes.

One invocation reads its input row -- footprint, decay (epsilon, alpha),
the explore draw, its tile mask, the concurrency mask, the accelerator's
profile row, the action mask and two rows of Gumbel noise, plus five
integer columns -- reads and writes one Q-table row, and writes its trace
row.  Those bytes are the least any lowering must move.  The operations
count the step's arithmetic over the same widths: the three per-tile
reductions of the sensed state and the tile overlap (``4 * T * tiles``
multiply-adds), the action selection and update over the ``A`` actions,
and a fixed body of scalar timing-model and reward arithmetic.  The count
depends only on the widths, never on how a program lowers the step.
"""
from __future__ import annotations

N_ACTIONS = 4
PROFILE_WIDTH = 9
TRACE_COLS = 6          # mode, state, action, exec cycles, off-chip, reward
SERVE_COLS = 13         # the trace plus the admission columns
SCALAR_OPS = 150        # timing model and reward, per invocation


def per_invocation(n_threads: int, n_tiles: int, serve: bool = False,
                   queue_cap: int = 0) -> tuple[int, int]:
    """(bytes, operations) one invocation needs, f32/int32 words."""
    a = N_ACTIONS
    in_f = 4 + n_tiles + n_threads + PROFILE_WIDTH + 3 * a
    in_i = 5
    out = SERVE_COLS if serve else TRACE_COLS
    words = in_f + in_i + out + 2 * a
    ops = 2 * 4 * n_threads * n_tiles + 6 * a + SCALAR_OPS
    if serve:
        words += 3                       # arrival time, deadline, priority
        ops += 2 * 4 * queue_cap         # four admission attempts
    return 4 * words, ops


def roofline_seconds(total_bytes: float, total_ops: float,
                     peaks: dict) -> tuple[float, str]:
    """Least time at the chip's peaks, and which peak bounds it."""
    t_b = total_bytes / peaks["hbm_bytes_per_s"]
    t_o = total_ops / peaks["flops_per_s"]
    return (t_b, "bytes") if t_b >= t_o else (t_o, "operations")
