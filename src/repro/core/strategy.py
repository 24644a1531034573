"""Strategy protocol shared by the proposed method and all baselines.

A strategy owns three callables:

  * ``init(key, data) -> state`` — build the initial server/client state
    (including any pre-training round, e.g. the paper's collaboration
    round or nothing for FedAvg);
  * ``round(state, data, key, cohort=None) -> (state, metrics)`` — one
    communication round (local training + PS aggregation); jitted
    internally. ``cohort`` is a fixed-shape padded
    :class:`~repro.federated.participation.Cohort` (``(indices, mask)``
    with sentinel-index zero-weight pad slots), a plain sorted index
    array (normalized to an unpadded all-real cohort), or ``None`` for
    full participation. With a cohort, only the masked slots are
    gathered/trained/uploaded; the aggregation mixes with the masked
    row-renormalized W and absent clients keep their last personalized
    model (the fused ``masked_mix_scatter`` kernel writes only the real
    cohort rows of the stacked state, whose buffer the jitted round
    *donates* — callers must not reuse the pre-round state).
    ``cohort=None`` must follow the exact dense full-participation path
    so that fraction=1.0 stays bit-exact with the pre-cohort engine.
  * ``eval_params(state) -> stacked params`` — the per-client models that
    should be evaluated (personalized where the method has them).

All eleven strategies build ``round`` from the single dispatch helper
:func:`repro.core.baselines.common.cohort_round`, so the padded-cohort
contract lives in one place. Cohorts are drawn by
:mod:`repro.federated.participation` and threaded by the simulation loop;
the static slot count means one policy compiles ONE round shape — the
availability sampler included (its short rounds are masked, not
truncated).

``metrics`` may include per-round diagnostics (e.g. downlink stream
count, which feeds the §V-D comm model in the Fig. 5 benchmark).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

REGISTRY: Dict[str, Callable[..., "Strategy"]] = {}


@dataclasses.dataclass
class Strategy:
    name: str
    init: Callable[..., Any]
    round: Callable[..., Any]
    eval_params: Callable[[Any], Any]
    # downlink streams per round, for the comm model ("broadcast",
    # "groupcast", "unicast", "client_mixing") and the stream count.
    comm_scheme: str = "broadcast"
    num_streams: int | None = None
    # optional ``skip_round(state) -> state`` hook the simulation loop
    # calls on rounds nobody attends (an all-offline availability
    # cohort): time still passes for per-client bookkeeping — e.g. the
    # streaming W refresh's staleness counters advance — even though no
    # training/aggregation runs. None = skipped rounds don't touch state.
    skip_round: Callable[[Any], Any] | None = None
    # True when the strategy was built with ``FedConfig.faults`` — the
    # simulation loop's fail-fast non-finite guard stands down (injected
    # NaN/Inf uploads are expected and absorbed by the finite guard;
    # raising on them would defeat the graceful-degradation test).
    injects_faults: bool = False
    # the strategy's declared wire layout (a
    # :class:`repro.federated.transport.WireSchema`): named uplink and
    # downlink streams with per-stream widths and codings, consumed by
    # the transport stages and the §V-D byte pricing
    # (``comm_model.wire_bytes``). None only for strategies that reject
    # ``FedConfig.transport`` (ucfl_parallel).
    wire_schema: Any = None


def register(name):
    def deco(fn):
        REGISTRY[name] = fn
        return fn
    return deco


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Paper §V-A hyperparameters.

    ``chunk_size`` bounds peak client-axis memory: local SGD runs as a
    sequential loop over chunks of that many vmapped clients (see
    :func:`repro.federated.client.make_federated_local_sgd`); ``None``
    keeps the single monolithic vmap.

    ``mesh`` shards the cohort/client axis across devices (see
    :mod:`repro.federated.mesh`): a 1-D ``jax.sharding.Mesh`` over a
    ``clients`` axis, an int shard count, or ``"auto"`` for all local
    devices. Local SGD runs shard_mapped with the cohort slots
    partitioned across the mesh (``chunk_size`` then chunks *within*
    each shard) and the cohort dispatcher pads slot counts to a shard
    multiple; the (c, c) mix and the fused scatter stay replicated.
    ``None`` keeps the single-device path bit-exact.

    ``async_buffer`` (a :class:`repro.federated.async_buffer.AsyncConfig`,
    or ``None`` = off) opts cohort rounds into the buffered-async
    FedBuff-style server: uploads land in a fixed-shape pending buffer
    and the PS applies them — staleness-discounted by
    ``(1+τ)^{-α}`` — once ``flush_k`` have accumulated, instead of
    barrier-mixing every round. Supported by the strategies whose PS
    step is the masked row aggregation (ucfl full/clustered and the
    FedAvg family); the rest raise at construction. Requires cohort
    rounds (a participation config) — the dense ``cohort=None`` path is
    the bulk-synchronous barrier by definition. ``None`` (the default)
    keeps every existing trajectory bit-identical.

    ``shard_state`` row-shards the (m, ·) stacked server state across the
    ``mesh`` (see the row-sharded section of :mod:`repro.federated.mesh`):
    device k owns rows ``[k·m/s, (k+1)·m/s)`` of every state leaf, the
    round-start gather and round-end scatter route each cohort row to its
    owner shard inside the jitted round, and the only model-sized
    collectives are O(c·d). Requires a mesh with ``m % num_shards == 0``
    and cohort rounds (the dense path raises); ``False`` (the default)
    keeps the replicated layout bit-exact. Results match the replicated
    layout within float32 round-off (the cohort psum can associate
    additions differently).

    ``w_refresh`` (a :class:`repro.core.similarity.RefreshConfig`, or
    ``None`` = off) opts the W-owning strategies (ucfl, clustered ucfl,
    ucfl_parallel) into the streaming W refresh: every masked cohort
    round folds the cohort's gradient proxies into running Δ/σ² buffers
    and recomputes W on device, with per-client staleness counters in
    the round metrics. Off (the default, the paper's compute-W-once
    rule) keeps every existing trajectory bit-identical; the dense
    ``cohort=None`` path never refreshes either way. Strategies without
    a W ignore the knob.

    ``faults`` (a :class:`repro.federated.faults.FaultConfig`, or
    ``None`` = off) opts cohort rounds into deterministic fault
    injection — Byzantine uploads from a static seed-drawn attacker
    set, NaN/Inf corruption, mid-round upload drops — applied as
    fixed-shape masked transforms inside the jitted round (see
    :mod:`repro.federated.faults`). ``robust`` (a
    :class:`repro.core.aggregation.RobustConfig`, or ``None`` = off)
    turns on the Byzantine-robust upload rewrite — coordinate
    trimmed-mean/median, norm clipping, (multi-)Krum selection — ahead
    of the strategy's masked mix. Either knob also arms the finite
    guard that demotes non-finite upload rows to masked pad slots, so a
    poisoned round degrades gracefully instead of NaN-ing the state.
    Both require cohort rounds (the dense ``cohort=None`` path raises);
    ``None``/``None`` (the defaults) keep every existing trajectory
    bit-identical.

    ``transport`` (a :class:`repro.federated.transport.TransportConfig`,
    or ``None`` = off) opts cohort rounds into quantized wire transport.
    Each strategy declares a :class:`repro.federated.transport.WireSchema`
    — named uplink and downlink streams, each a slab-width slice with
    its own coding and its own error-feedback accumulator — and the
    transport stages run per stream inside the same jitted round (one
    compiled shape): ``delta`` streams travel int8/fp8 per-chunk-scaled
    with EF (client-side on the uplink, server-side on the downlink),
    ``raw`` streams stay float32, ``relay`` streams forward payloads
    another hop already quantized. Compression noise stays unbiased —
    including under ``w_refresh``, whose Δ/σ² estimation observes the
    dequantized uploads. Every strategy supports the knob except
    ``ucfl_parallel`` (no PS wire to compress — it raises at
    construction; see the capability matrix in
    :mod:`repro.federated.transport`). Requires cohort rounds (the dense
    path has no upload stage). ``None`` (the default) keeps every
    existing trajectory bit-identical.

    ``topology`` (a :class:`repro.federated.topology.Topology`, or
    ``None`` = off) opts cohort rounds into the two-tier hierarchical
    engine: clients are statically assigned to edge aggregators, the
    tier-1 masked mix runs per edge over fixed-shape padded per-edge
    slots (the Cohort/sentinel trick one level up), and only the
    ``(E, ·)`` edge-aggregate slab crosses the edge↔PS backhaul for the
    mass-weighted tier-2 combine — an exact factorization of the flat
    linear rules, so accuracy matches while PS-side traffic shrinks
    from ``c`` uploads to ``E·k`` aggregates (priced by
    ``comm_model.SystemParams.tiers``). Supported where the PS rule is
    linear in the uploads (the FedAvg family and clustered ucfl,
    composing with ``transport``, ``faults``/``robust``, ``w_refresh``
    and replicated ``mesh``); per-client unicast mixes (ucfl full,
    fedfomo, ...), ``shard_state`` and ``async_buffer`` raise
    NotImplementedError at construction with a capability note.
    Requires cohort rounds (the dense path has no per-edge upload
    stage). ``None`` (the default) keeps every existing trajectory
    bit-identical.

    ``selection`` (a :class:`repro.federated.participation.SelectionConfig`,
    or ``None`` = off) declares Pareto-biased cohort selection: per-round
    sampling mass biased by compute speed, link quality, a
    battery/diurnal availability trace, and data value, with a
    deterministic round-robin fairness lane bounding every
    positive-mass client's selection window. Drivers thread it into the
    sampler via :func:`repro.federated.participation.with_selection`
    (the strategy itself never draws cohorts). ``None`` keeps the
    configured sampler untouched.
    """
    lr: float = 0.1
    momentum: float = 0.9
    epochs: int = 1
    batch_size: int = 50
    chunk_size: int | None = None
    mesh: Any = None
    shard_state: bool = False
    w_refresh: Any = None
    async_buffer: Any = None
    faults: Any = None
    robust: Any = None
    transport: Any = None
    topology: Any = None
    selection: Any = None
