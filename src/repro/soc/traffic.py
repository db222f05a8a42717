"""Continuous multi-tenant traffic for the serving path — spec + arrivals.

The episodic environments replay a *closed* world: a compiled schedule of
``n_steps`` invocations, then the world ends.  Serving (``vecenv.ServeEnv``)
opens it: requests arrive over continuous time from a stochastic process,
compete for bounded per-accelerator admission queues, and are shed when
their deadline cannot be met.  This module owns the arrival side of that
loop as a scalar-pytree spec plus one pre-sampled arrival table per chunk —
the ``qlearn.SelectNoise`` / ``faults.StepFault`` pattern:

  * :class:`TrafficSpec` is a pytree of scalar ``jnp`` leaves (plus small
    per-tenant vectors) carrying its OWN threefry key, so traffic streams
    compose with the episode/serving key protocol without perturbing it,
    and sweeping any knob (rate, burstiness, deadlines ...) reuses the
    compiled program — the leaves are traced, never baked in;
  * :func:`sample_arrivals` lowers a spec to an :class:`Arrivals` table for
    one chunk of ``n_requests`` offered requests in one batched draw —
    arrival times, the schedule row each request invokes, tenant, absolute
    deadline and priority.  The table rides the serving scan's xs; no host
    Python ever runs per-request;
  * the DES mirror (``SoCSimulator.serve``) consumes the *same* table via
    ``np.asarray``, so the fidelity cross-check replays bit-identical
    arrivals through the host event loop.

Arrival process: a 2-state Markov-modulated Poisson process (MMPP-2).  The
chain sits in a *calm* state (rate ``rate``) or a *burst* state (rate
``rate * burst_rate``) and flips with per-arrival probabilities
``p_burst`` (calm -> burst) and ``p_calm`` (burst -> calm); exponential
inter-arrival gaps are inverse-CDF transforms of pre-sampled uniforms, so
``burst_rate == 1`` degenerates to a plain Poisson stream regardless of
the chain (the :func:`poisson` constructor).

Tenancy: ``mix`` weights a K-way categorical tenant draw (Gumbel argmax —
one pre-sampled ``(n, K)`` table).  Tenant ``k`` invokes rows from its
contiguous slice of the compiled schedule (``[k*S/K, (k+1)*S/K)``), so a
multi-tenant stream exercises disjoint working sets; per-tenant relative
``deadline`` cycles (``<= 0`` disables — the request never sheds on time)
and ``priority`` in [0, 1] (weights each tenant's share of the admission
queue via ``prio_reserve``) complete the request.

The serving-robustness knobs (``backoff``, ``overload_frac``,
``pressure_beta``, ``prio_reserve``) live on the spec too: they are
properties of the offered traffic contract (how hard to retry, when the
service may degrade), and keeping them here means one pytree configures a
whole serving run.  ``vecenv.build_serve_fn`` threads them into the fused
step's :class:`~repro.kernels.soc_step.ref.ServeParams`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Deadline sentinel: far beyond any reachable simulated-cycle timestamp,
# finite so the admission compare (start <= deadline) stays IEEE-ordinary.
NO_DEADLINE = np.float32(1e30)


class TrafficSpec(NamedTuple):
    """Scalar pytree describing one offered-traffic contract.

    All leaves are traced jnp scalars / small vectors — sweeping any of
    them (offered-load sweeps, deadline sweeps) hits the jit cache.  The
    spec carries its OWN key; chunked serving folds the chunk index into
    it (``chunk_key``) so every chunk draws fresh arrivals while the
    serving loop's main key stream is untouched.

    * ``rate`` — calm-state arrival rate in requests per cycle;
    * ``burst_rate`` — burst-state rate multiplier (1 = plain Poisson);
    * ``p_burst`` / ``p_calm`` — per-arrival MMPP-2 flip probabilities
      (calm -> burst, burst -> calm);
    * ``mix`` — (K,) tenant mix weights (need not be normalized);
    * ``deadline`` — (K,) per-tenant relative deadline in cycles from
      arrival; ``<= 0`` disables deadline shedding for that tenant;
    * ``priority`` — (K,) per-tenant priority in [0, 1]; with
      ``prio_reserve > 0``, low-priority tenants see a smaller effective
      admission queue (``cap * (1 - prio_reserve * (1 - priority))``);
    * ``backoff`` — base retry backoff in cycles (bounded exponential —
      the PR-7 fault-retry math, ``faults.backoff_cycles``);
    * ``overload_frac`` — shed-rate EMA level that trips the overload
      watchdog (forced NON_COH + epsilon reopen); 0 disables;
    * ``pressure_beta`` — EMA coefficient of the shed-pressure monitor;
    * ``key`` — (2,) uint32 threefry key owning all traffic randomness.
    """

    rate: jnp.ndarray           # () f32 requests / cycle (calm)
    burst_rate: jnp.ndarray     # () f32 burst multiplier
    p_burst: jnp.ndarray        # () f32 calm -> burst flip prob
    p_calm: jnp.ndarray         # () f32 burst -> calm flip prob
    mix: jnp.ndarray            # (K,) f32 tenant weights
    deadline: jnp.ndarray       # (K,) f32 relative deadline cycles
    priority: jnp.ndarray       # (K,) f32 in [0, 1]
    backoff: jnp.ndarray        # () f32 retry backoff cycles
    overload_frac: jnp.ndarray  # () f32 watchdog trip level (0 = off)
    pressure_beta: jnp.ndarray  # () f32 shed-EMA coefficient
    prio_reserve: jnp.ndarray   # () f32 queue fraction priority-gated
    key: jnp.ndarray            # (2,) uint32


def poisson(rate, *, deadline=0.0, priority=1.0, backoff=0.0,
            overload_frac=0.0, pressure_beta=0.05, prio_reserve=0.0,
            key=None, seed: int = 0) -> TrafficSpec:
    """Single-tenant Poisson traffic at ``rate`` requests per cycle.

    The degenerate MMPP (``burst_rate=1``): the fidelity-scoped stream the
    DES cross-check runs on.  ``deadline``/``priority`` may be scalars or
    (K,) arrays (scalars become one tenant)."""
    return bursty(rate, burst_rate=1.0, p_burst=0.0, p_calm=1.0,
                  mix=jnp.ones(np.shape(deadline) or (1,), jnp.float32),
                  deadline=deadline, priority=priority, backoff=backoff,
                  overload_frac=overload_frac, pressure_beta=pressure_beta,
                  prio_reserve=prio_reserve, key=key, seed=seed)


def bursty(rate, *, burst_rate=4.0, p_burst=0.05, p_calm=0.25,
           mix=(1.0,), deadline=0.0, priority=1.0, backoff=0.0,
           overload_frac=0.0, pressure_beta=0.05, prio_reserve=0.0,
           key=None, seed: int = 0) -> TrafficSpec:
    """MMPP-2 bursty multi-tenant traffic.

    ``mix`` fixes K; scalar ``deadline``/``priority`` broadcast across
    tenants.  Defaults flip into ~4x bursts lasting ~4 arrivals every ~20
    arrivals."""
    f32 = jnp.float32
    mix = jnp.atleast_1d(jnp.asarray(mix, f32))
    k = mix.shape[0]
    return TrafficSpec(
        rate=jnp.asarray(rate, f32),
        burst_rate=jnp.asarray(burst_rate, f32),
        p_burst=jnp.asarray(p_burst, f32),
        p_calm=jnp.asarray(p_calm, f32),
        mix=mix,
        deadline=jnp.broadcast_to(jnp.asarray(deadline, f32), (k,)),
        priority=jnp.broadcast_to(jnp.asarray(priority, f32), (k,)),
        backoff=jnp.asarray(backoff, f32),
        overload_frac=jnp.asarray(overload_frac, f32),
        pressure_beta=jnp.asarray(pressure_beta, f32),
        prio_reserve=jnp.asarray(prio_reserve, f32),
        key=key if key is not None else jax.random.PRNGKey(seed))


def chunk_key(spec: TrafficSpec, chunk: int) -> TrafficSpec:
    """The spec for chunk ``chunk`` of a long-lived stream: same contract,
    chunk-folded key — every chunk draws fresh arrivals deterministically
    (``fold_in``, the FaultSpec per-iteration protocol).  Host preparation
    of a serving chunk, so it runs under the ``cohm.prep`` span."""
    with jax.profiler.TraceAnnotation("cohm.prep"):
        return spec._replace(key=jax.random.fold_in(spec.key, chunk))


class Arrivals(NamedTuple):
    """One chunk's pre-sampled arrival table ((n_requests,) leaves).

    Rides the serving scan's xs; ``np.asarray`` of the same table drives
    the DES mirror, so both paths see bit-identical offered traffic.

    * ``t_arr`` — absolute arrival time in cycles (monotone increasing,
      continuing from ``t0``);
    * ``row`` — compiled-schedule row this request invokes (the request's
      accelerator, footprint and tile stripe are that row's);
    * ``tenant`` — tenant index in [0, K);
    * ``deadline`` — absolute latest admissible *start* time
      (:data:`NO_DEADLINE` when the tenant's deadline is disabled);
    * ``priority`` — the tenant's priority, clipped to [0, 1];
    * ``burst`` — the MMPP state that timed this arrival (diagnostics).
    """

    t_arr: jnp.ndarray     # (n,) f32 absolute cycles
    row: jnp.ndarray       # (n,) i32 schedule row
    tenant: jnp.ndarray    # (n,) i32
    deadline: jnp.ndarray  # (n,) f32 absolute cycles
    priority: jnp.ndarray  # (n,) f32 in [0, 1]
    burst: jnp.ndarray     # (n,) bool


def sample_arrivals(spec: TrafficSpec, n_requests: int, n_rows: int,
                    t0=0.0) -> Arrivals:
    """Draw one chunk of ``n_requests`` arrivals over ``n_rows`` schedule
    rows, starting the clock at ``t0``.

    Everything is pre-sampled in one batched draw from the spec's own key
    (4-way split: MMPP flips, gaps, row picks, tenant Gumbels); the only
    sequential piece is the K-independent 2-state chain — a scalar-carry
    ``lax.scan`` over pre-drawn uniforms, the same shape as
    ``qlearn``'s noise protocol.  ``n_requests`` and ``n_rows`` are
    static (shapes); every spec leaf is traced, so offered-load sweeps
    never retrace."""
    f32 = jnp.float32
    k_state, k_gap, k_row, k_ten = jax.random.split(spec.key, 4)
    u_state = jax.random.uniform(k_state, (n_requests,), f32)
    u_gap = jax.random.uniform(k_gap, (n_requests,), f32)
    u_row = jax.random.uniform(k_row, (n_requests,), f32)
    g_ten = jax.random.gumbel(k_ten, (n_requests, spec.mix.shape[0]), f32)

    # MMPP-2 state chain: the state in force for arrival i is the state
    # *after* applying flip i (a calm-started chunk's first arrival can
    # already be bursty).  burst_rate == 1 makes the chain timing-inert.
    def flip(high, u):
        high = jnp.where(high, u >= spec.p_calm, u < spec.p_burst)
        return high, high

    _, burst = jax.lax.scan(flip, jnp.zeros((), bool), u_state)
    rate_t = spec.rate * jnp.where(burst, spec.burst_rate, 1.0)
    # Inverse-CDF exponential gaps; log1p keeps u -> 0 exact and the rate
    # floor keeps a zero-rate spec finite (gaps become huge, not inf/nan).
    gaps = -jnp.log1p(-u_gap * np.float32(1 - 1e-7))
    gaps = gaps / jnp.maximum(rate_t, np.float32(1e-12))
    t_arr = jnp.asarray(t0, f32) + jnp.cumsum(gaps)

    # Tenant draw (Gumbel argmax == categorical(mix)) and the tenant's
    # contiguous schedule-row slice.  Slice bounds use static n_rows/K
    # host arithmetic per tenant via integer jnp ops on the traced index.
    kk = spec.mix.shape[0]
    logits = jnp.log(jnp.maximum(spec.mix, np.float32(1e-12)))
    tenant = jnp.argmax(logits[None, :] + g_ten, axis=-1).astype(jnp.int32)
    lo = (tenant * n_rows) // kk
    hi = ((tenant + 1) * n_rows) // kk
    span = jnp.maximum(hi - lo, 1)
    row = lo + jnp.floor(u_row * span.astype(f32)).astype(jnp.int32)
    row = jnp.clip(row, 0, n_rows - 1)

    dl_rel = spec.deadline[tenant]
    deadline = t_arr + jnp.where(dl_rel <= 0.0, NO_DEADLINE, dl_rel)
    priority = jnp.clip(spec.priority[tenant], 0.0, 1.0)
    return Arrivals(t_arr=t_arr, row=row, tenant=tenant, deadline=deadline,
                    priority=priority, burst=burst)
