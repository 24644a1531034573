"""Federated simulation engine: rounds loop + per-round evaluation.

Partial participation: ``run(..., participation=ParticipationConfig(...))``
draws a fixed-shape padded cohort per round (see
:mod:`repro.federated.participation`) and passes it to
``strategy.round(state, data, key, cohort)``. The cohort sampler uses its
own numpy seed stream, so the jax round keys — and hence the
``fraction=1.0`` trajectory — are identical to the dense engine's.
Because every cohort of a policy has the same slot count, the jitted
round compiles exactly once even when the availability sampler's
eligible set varies.

Round metrics flow through untouched: strategies running the streaming
W refresh (``FedConfig.w_refresh``) report the per-client ``staleness``
vector plus ``staleness_max``/``staleness_mean`` device scalars each
cohort round; ``verbose=True`` prints the scalar pair.

Timing: ``strategy.round`` is warmed up once (result discarded) before the
wall-clock timer starts, so ``History.wall_s`` measures steady-state
rounds, not XLA compilation. The warm-up key is ``fold_in``-derived and
does not consume the round key stream; the warm-up runs on a *copy* of
the state because the cohort round donates its stacked buffers. The
per-round evaluation passes are timed separately into ``History.eval_s``
and EXCLUDED from ``wall_s`` — eval frequency is a measurement choice,
not a property of the round engine, and benchmark consumers comparing
engines by ``wall_s`` must not see it.

Tracing: under ``jax.profiler.trace`` the run marks its host work with
the ``fl.`` spans of :mod:`repro.federated.spans` — ``fl.init``,
``fl.warmup``, and per round a profiler step ``fl.round`` holding
``fl.cohort``, ``fl.dispatch``, ``fl.check_finite`` and ``fl.evaluate``.

Evaluation: ``eval_chunk`` bounds the client axis of the per-round
accuracy pass with the same ``lax.map`` machinery as training, so eval
no longer materializes O(m · test_set) activations at once; pass
``eval_mesh`` (typically the same knob as ``FedConfig.mesh``) to shard
that pass across devices instead.

Sharding: a strategy built with ``FedConfig(mesh=...)`` (see
:mod:`repro.federated.mesh`) runs its cohort local SGD partitioned
across devices; the rounds loop itself is mesh-agnostic — the round
dispatcher pads slot counts to a shard multiple internally, and every
padded cohort of a policy still has ONE static shape, so the
one-compilation guarantee and the warm-up logic below hold unchanged
(sharded results match the unsharded engine within f32 round-off).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.federated import participation as part
from repro.federated import spans
from repro.federated.client import evaluate


@dataclasses.dataclass
class History:
    """Per-run eval trajectory + timing split.

    ``wall_s`` is the steady-state ROUND time only (warm-up/compilation
    excluded by the warm-up call, evaluation excluded by construction);
    ``eval_s`` holds the accumulated evaluation time separately.
    ``state`` is the strategy state after the last round.
    """

    strategy: str
    rounds: List[int]
    avg_acc: List[float]
    worst_acc: List[float]
    metrics: List[Dict[str, Any]]
    wall_s: float = 0.0
    eval_s: float = 0.0
    state: Any = None

    @property
    def final_avg(self):
        return self.avg_acc[-1]

    @property
    def final_worst(self):
        return self.worst_acc[-1]

    @property
    def best_avg(self):
        return max(self.avg_acc)

    @property
    def paired_best(self):
        """(avg, worst) evaluated at the argmax-average round.

        Tables 1/2 pair average and worst-user accuracy of ONE model;
        taking max() of each list independently would mix two different
        rounds' models.
        """
        i = int(np.argmax(self.avg_acc))
        return self.avg_acc[i], self.worst_acc[i]


def donation_safe_copy(state):
    """Copy the device-array leaves so a donating round can't eat them.

    The masked cohort round donates its stacked state buffers
    (``donate_argnums``) — the (m, ·) params trees AND, with the
    streaming W refresh on, the Δ/σ²/gradient-proxy/staleness buffers in
    ``state["refresh"]`` — so any caller of ``strategy.round`` that keeps
    the pre-round state alive — warm-ups, A/B comparisons from one start
    state, benchmarks — must run the round on a copy. This is the
    sanctioned helper for that (it copies every ``jax.Array`` leaf, the
    refresh buffers included).
    """
    return jax.tree.map(
        lambda x: x.copy() if isinstance(x, jax.Array) else x, state)


_donation_safe_copy = donation_safe_copy  # backward-compatible alias


@jax.jit
def _client_rows_finite(stacked):
    """(m,) bool: every leaf of client i's eval params is finite."""
    def leaf_finite(x):
        return jnp.all(jnp.isfinite(x.astype(jnp.float32)),
                       axis=tuple(range(1, x.ndim)))
    leaves = [leaf_finite(x) for x in jax.tree.leaves(stacked)]
    return jnp.all(jnp.stack(leaves, axis=0), axis=0)


def _check_finite_state(strategy, state, rnd):
    """Fail fast on non-finite models instead of silently training on
    NaNs for the rest of the run. Raises with the round index and the
    offending client rows; runs only at eval rounds (one host sync) and
    stands down when the strategy itself injects faults
    (``Strategy.injects_faults`` — the finite guard absorbs those)."""
    finite = np.asarray(_client_rows_finite(strategy.eval_params(state)))
    if not finite.all():
        bad = np.nonzero(~finite)[0].tolist()
        raise RuntimeError(
            f"non-finite model state after round {rnd} "
            f"(strategy {strategy.name!r}, client rows {bad}): a NaN/Inf "
            "upload leaked into aggregation. Enable FedConfig.faults / "
            "FedConfig.robust for guarded degradation, or pass "
            "check_finite=False to simulation.run to opt out")


def _warm_up(strategy, state, data, key, participation, check_finite):
    """One discarded round on a copy of ``state``, synced, so that
    ``strategy.round`` (and the finite check) compile before the timer."""
    m = data.num_clients
    wcohort = part.sample_cohort(participation, 1, m, data.n)
    if wcohort is not None and len(wcohort) == 0:
        # round 1 is all-offline; every cohort of a policy shares one
        # compiled shape, so warm up with a synthetic one-member
        # cohort of the same slot count instead of skipping (which
        # would push the compile into the timed region)
        idx = np.full(wcohort.num_slots, m, np.int32)
        idx[0] = 0
        mask = np.zeros(wcohort.num_slots, bool)
        mask[0] = True
        wcohort = part.Cohort(indices=idx, mask=mask)
    wstate, _ = strategy.round(
        donation_safe_copy(state), data,
        jax.random.fold_in(key, 0x5EED), wcohort)
    jax.block_until_ready(wstate)
    if check_finite:  # the eval-round finite check runs on the clock
        jax.block_until_ready(
            _client_rows_finite(strategy.eval_params(wstate)))


def run(strategy, apply_fn, data, key, *, rounds: int, eval_every: int = 1,
        verbose: bool = False, participation: part.ParticipationConfig | None
        = None, warmup: bool = True, eval_chunk: int | None = None,
        eval_mesh=None, check_finite: bool | None = None,
        selection=None) -> History:
    m = data.num_clients
    if selection is not None:
        # Pareto-biased cohort draws (FedConfig.selection): rewrite the
        # participation policy to the pareto sampler carrying the per-
        # client bias factors; the strategy never draws cohorts itself
        participation = part.with_selection(participation, selection)
    key, ikey = jax.random.split(key)
    with spans.span(spans.INIT):
        state = strategy.init(ikey, data)
    hist = History(strategy.name, [], [], [], [])
    # None = on unless the strategy deliberately injects faults (its
    # finite guard owns degradation there; raising would defeat it)
    if check_finite is None:
        check_finite = not strategy.injects_faults

    if warmup:  # compile strategy.round outside the timed region
        with spans.span(spans.WARMUP):
            _warm_up(strategy, state, data, key, participation, check_finite)

    t0 = time.perf_counter()

    def do_eval(rnd, metrics):
        if check_finite:
            with spans.span(spans.CHECK_FINITE):
                _check_finite_state(strategy, state, rnd)
        with spans.span(spans.EVALUATE):
            te = time.perf_counter()
            accs = np.asarray(
                evaluate(apply_fn, strategy.eval_params(state), data.x_test,
                         data.y_test, batch=eval_chunk, mesh=eval_mesh)
            )
            hist.eval_s += time.perf_counter() - te
        hist.rounds.append(rnd)
        hist.avg_acc.append(float(accs.mean()))
        hist.worst_acc.append(float(accs.min()))
        hist.metrics.append(metrics)
        if verbose:
            stale = ("" if "staleness_max" not in metrics else
                     f" stale_max={int(metrics['staleness_max'])}"
                     f" stale_mean={float(metrics['staleness_mean']):.1f}")
            print(f"[{strategy.name}] round {rnd:4d} "
                  f"avg={accs.mean():.4f} worst={accs.min():.4f} "
                  f"cohort={metrics.get('cohort_size', m)}{stale}")

    metrics: Dict[str, Any] = {}
    for rnd in range(1, rounds + 1):
        # each iteration is one profiler step, so XProf shows rounds
        with jax.profiler.StepTraceAnnotation(spans.ROUND, step_num=rnd):
            with spans.span(spans.COHORT):
                key, rkey = jax.random.split(key)
                cohort = part.sample_cohort(participation, rnd, m, data.n)
            with spans.span(spans.DISPATCH):
                if cohort is not None and len(cohort) == 0:
                    # nobody available this round: the server idles and
                    # no training/aggregation runs — but time still
                    # passes for per-client bookkeeping (e.g. the
                    # streaming W refresh's staleness counters), which
                    # the strategy's skip hook owns. Skipping state
                    # entirely here used to freeze the counters for
                    # rounds nobody attends.
                    if strategy.skip_round is not None:
                        state = strategy.skip_round(state)
                    metrics = {"streams": 0, "cohort_size": 0,
                               "skipped": True}
                else:
                    state, metrics = strategy.round(state, data, rkey,
                                                    cohort)
            if rnd % eval_every == 0 or rnd == rounds:
                do_eval(rnd, metrics)
    hist.wall_s = time.perf_counter() - t0 - hist.eval_s
    hist.state = state
    return hist


def run_trials(make_strategy, apply_fn, data_fn, *, trials: int, rounds: int,
               seed: int = 0, eval_every: int = 1, participation=None,
               selection=None):
    """Average over independent trials (paper reports 5-trial means).

    The reported (avg, worst) pair comes from one model per trial — the
    argmax-average eval round — matching how Tables 1/2 pair them.
    """
    finals, worsts, hists = [], [], []
    for t in range(trials):
        key = jax.random.PRNGKey(seed + 1000 * t)
        dkey, skey = jax.random.split(key)
        data = data_fn(dkey)
        strat = make_strategy(t)
        h = run(strat, apply_fn, data, skey, rounds=rounds,
                eval_every=eval_every, participation=participation,
                selection=selection)
        avg, worst = h.paired_best
        finals.append(avg)
        worsts.append(worst)
        hists.append(h)
    return {
        "avg_mean": float(np.mean(finals)),
        "avg_std": float(np.std(finals)),
        "worst_mean": float(np.mean(worsts)),
        # the paper's worst-node headline metric needs its spread too —
        # reporting avg_std without worst_std hid the (typically much
        # larger) variance of the minimum
        "worst_std": float(np.std(worsts)),
        "histories": hists,
    }
