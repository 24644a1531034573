"""Quantized wire transport (``FedConfig.transport``) over declared streams.

PR 8 compressed one hard-coded ``(c, d)`` uplink delta slab. The wire
layer is now schema-driven: every strategy declares a
:class:`WireSchema` — named uplink and downlink :class:`Stream` slices of
the 128-aligned slab, each with its own quantization eligibility
(``coding``) and its own error-feedback accumulator slice — and
:func:`make_wire_stage` builds the per-stream quantize→dequantize stage
for either direction. ``transport=None`` keeps every strategy's exact
stage-free trace, bit-for-bit.

Stream codings
--------------
  * ``"delta"`` — a per-receiver model/state delta: quantized int8/fp8
    per chunk with error feedback (the only coding that owns EF state).
  * ``"raw"``   — never compressed; 4 B/coordinate on the wire and a
    pass-through in the stage (the receiver has no shared reference to
    delta-code against, and stateless absolute quantization of
    weight-scale values would inject ~``max|chunk|/254`` noise — outside
    the 2e-3 drift budget the transport tests pin).
  * ``"relay"`` — the receiver downloads a payload some OTHER hop
    already quantized (FedFomo peers fetch the cohort's quantized
    uploads): priced at the compressed width, but no second stage runs —
    re-quantizing an already-dequantized relay would double the noise.

Per-strategy stream/capability matrix
-------------------------------------
=============  ==============================  =============================
strategy       uplink streams                  downlink streams
=============  ==============================  =============================
fedavg         delta                           broadcast: delta (server EF)
fedprox        delta                           broadcast: delta (server EF)
local          delta                           — (no downlink)
oracle         delta                           groupcast: raw
ucfl (full)    delta                           personalized: delta (server
                                               EF rows per client)
ucfl (clust.)  delta                           centroids: raw
scaffold       delta + control_delta           model: delta, control: delta
                                               (one shared server EF row)
ditto          global_delta                    broadcast: delta (the
                                               personal model never leaves
                                               the client)
pfedme         w_delta                         broadcast: raw (the β-mix
                                               average has no shared
                                               receiver reference)
fedfomo        delta                           peer_models: relay
cfl            delta (split stats consume the  centroids: raw
               dequantized deltas)
ucfl_parallel  UNSUPPORTED — the m× per-stream update stack has no wire
               slab (:func:`unsupported` raises at construction)
=============  ==============================  =============================

Buffered-async composition: the uplink stage runs before the deposit
(the pending buffer holds what the wire carried); the async DOWNLINK
stays raw f32 — a flush rewrites arbitrary subsets of rows, so there is
no per-receiver reference to delta-code against.

Two-tier topology composition (``FedConfig.topology``): the transport
stage dequantizes the cohort's uploads BEFORE the tier-1 per-edge mix,
so the tiered engine consumes the same post-wire slab as the flat one
and every supported (strategy, transport) pair above composes with a
topology unchanged — the client→edge hop carries the quantized wire,
the edge→PS hop carries f32 partial aggregates (priced per tier by
``comm_model.SystemParams.tiers`` and the ``ps_*_bytes_per_round``
backhaul counters). Topology itself is supported only where the PS mix
factorizes over per-edge partial sums — fedavg, fedprox, and clustered
ucfl; the rest raise at construction
(:func:`repro.federated.topology.unsupported`), as do
topology×shard_state and topology×async_buffer.

Error feedback: each DIRECTION keeps one f32 accumulator slab spanning
the concatenated aligned stream widths — ``(m, Σ dim_aligned)`` per
client on the uplink, ``(1, Σ)`` (broadcast) or ``(m, Σ)`` (unicast) on
the server for the downlink. A round quantizes ``delta + ef`` per stream
and carries each stream's new residual forward, so the long-run applied
update is unbiased per stream — on a constant delta the applied values
telescope to the truth within one quantization step (pinned in
tests/test_transport.py and, per stream, tests/test_wire_schema.py).
This is what keeps compression noise out of the streaming Δ/σ²
estimation under ``FedConfig.w_refresh``: the refresh observes the
dequantized upload the server actually received.

Downlink wire format
--------------------
A compressed (``delta``) downlink stream ships, per receiver group
(1 broadcast row, or one row per unicast receiver): ``width`` payload
bytes (1 B/coordinate, int8 and fp8-e4m3 alike) plus one f32 scale per
``chunk`` coordinates — ``width + 4·ceil(width/chunk)`` vs ``4·width``
raw, the same ~3.9× reduction as the uplink at the default chunk=128.
The server-side EF accumulator makes the compressed broadcast unbiased
exactly like the client-side EF makes the upload unbiased. ``raw``
streams ship ``4·width``; ``relay`` streams are priced at the compressed
width of the payload their source hop shipped. Pricing lives in
:func:`repro.core.comm_model.wire_bytes`.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.federated import spans
from repro.kernels import ops

_QMAX = {"int8": 127.0, "fp8": 448.0}  # fp8 = e4m3 finite max

_CODINGS = ("delta", "raw", "relay")


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """Wire compression knobs (both directions share one config).

    kind: ``"int8"`` (symmetric round-to-nearest) or ``"fp8"``
      (e4m3 cast, per-chunk rescaled to the e4m3 range).
    chunk: coordinates sharing one f32 scale. Must divide every
      ``delta`` stream's aligned slab width; the default 128 equals the
      kernel lane alignment (``ops.ALIGN``), so any ``dim_aligned``
      stream chunks evenly.
    """

    kind: str = "int8"
    chunk: int = 128

    def __post_init__(self):
        if self.kind not in _QMAX:
            raise ValueError(
                f"TransportConfig.kind must be one of {sorted(_QMAX)}, got {self.kind!r}",
            )
        if int(self.chunk) <= 0:
            raise ValueError("TransportConfig.chunk must be positive")


@dataclasses.dataclass(frozen=True)
class Stream:
    """One named slice of a direction's wire slab.

    width: TRUE coordinate count (what the wire prices); the slab slice
      is the 128-aligned ``width_aligned``, whose zero tail quantizes to
      exact zeros.
    coding: ``"delta"`` (quantized, owns an EF slice), ``"raw"``
      (pass-through, 4 B/coord), or ``"relay"`` (priced compressed, no
      stage — see the module docstring).
    """

    name: str
    width: int
    coding: str = "delta"

    def __post_init__(self):
        if self.coding not in _CODINGS:
            raise ValueError(
                f"Stream.coding must be one of {_CODINGS}, got {self.coding!r}",
            )
        if int(self.width) < 0:
            raise ValueError(f"Stream.width must be >= 0, got {self.width}")

    @property
    def width_aligned(self) -> int:
        return ops.aligned_dim(int(self.width)) if self.width else 0


@dataclasses.dataclass(frozen=True)
class WireSchema:
    """A strategy's declared wire layout (see the capability matrix)."""

    strategy: str
    uplink: tuple = ()
    downlink: tuple = ()

    def streams(self, direction: str) -> tuple:
        if direction not in ("uplink", "downlink"):
            raise ValueError(f"unknown wire direction {direction!r}")
        return self.uplink if direction == "uplink" else self.downlink

    def width(self, direction: str) -> int:
        """TRUE coordinate count of the direction's concatenated streams."""
        return sum(int(s.width) for s in self.streams(direction))

    def width_aligned(self, direction: str) -> int:
        """Slab width of the direction's concatenated aligned slices."""
        return sum(s.width_aligned for s in self.streams(direction))

    def slices(self, direction: str) -> tuple:
        """(lo, hi) aligned-slab slice per stream, in declaration order."""
        out, lo = [], 0
        for s in self.streams(direction):
            out.append((lo, lo + s.width_aligned))
            lo += s.width_aligned
        return tuple(out)


def single_delta_schema(strategy: str, dim: int, *, downlink=()) -> WireSchema:
    """The common one-uplink-delta schema (FedAvg family, ucfl, ...)."""
    return WireSchema(
        strategy,
        uplink=(Stream("delta", dim),),
        downlink=downlink,
    )


def unsupported(transport, strategy: str, why: str):
    """Uniform construction-time capability error for schema-less wires.

    Strategies that cannot declare a :class:`WireSchema` (only
    ucfl_parallel's m× column mix remains) call this instead of the old
    ad-hoc ``reject_transport``; the message points at the capability
    matrix in this module's docstring.
    """
    if transport is not None:
        raise NotImplementedError(
            f"FedConfig.transport is not supported by {strategy}: {why} — "
            "this strategy declares no WireSchema (see the per-strategy "
            "stream/capability matrix in repro/federated/transport.py)"
        )


def quantize(x, cfg: TransportConfig):
    """(…, d) f32 -> (q, scale): q (…, d/chunk, chunk) in the wire dtype,
    scale (…, d/chunk, 1) f32 per chunk."""
    d = x.shape[-1]
    chunk = int(cfg.chunk)
    if d % chunk:
        msg = f"transport chunk {chunk} does not divide the slab width {d}"
        raise ValueError(msg + " (the aligned slab always chunks evenly at chunk=128)")
    xs = x.reshape(x.shape[:-1] + (d // chunk, chunk))
    scale = jnp.max(jnp.abs(xs), axis=-1, keepdims=True) / _QMAX[cfg.kind]
    # all-zero chunks (e.g. the slab's aligned tail) quantize to exact 0
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
    if cfg.kind == "int8":
        q = jnp.clip(jnp.round(xs / scale), -127.0, 127.0).astype(jnp.int8)
    else:  # fp8
        q = (xs / scale).astype(jnp.float8_e4m3fn)
    return q, scale


def dequantize(q, scale):
    """Inverse of :func:`quantize` up to the quantization error."""
    xs = q.astype(jnp.float32) * scale
    return xs.reshape(xs.shape[:-2] + (xs.shape[-2] * xs.shape[-1],))


def roundtrip(x, cfg: TransportConfig):
    """What the receiver decodes from payload ``x``."""
    return dequantize(*quantize(x, cfg))


def _check_transport(transport):
    if not isinstance(transport, TransportConfig):
        got = type(transport).__name__
        raise TypeError(f"FedConfig.transport must be a TransportConfig or None, got {got}")


def make_stage(transport):
    """Build the single-slab transport stage, or ``None`` when off.

    The pre-schema primitive (a :func:`make_wire_stage` over one
    full-width ``delta`` stream is bit-identical): ``stage(pre, post,
    ef) -> (post', ef')`` over (c, d) cohort slabs — quantize
    ``(post - pre) + ef`` as the wire delta, reconstruct
    ``post' = pre + dequant`` (the payload the receiver decodes), and
    carry the residual in ``ef'``. Runs BEFORE the fault/robust upload
    stage — faults corrupt, and robust rules sanitize, the payload the
    wire actually carried.
    """
    if transport is None:
        return None
    _check_transport(transport)

    def stage(pre, post, ef):
        carry = (post - pre) + ef
        deq = roundtrip(carry, transport)
        return pre + deq, carry - deq

    return stage


def make_wire_stage(schema: WireSchema, transport, direction: str = "uplink"):
    """Build one direction's per-stream transport stage, or ``None``.

    ``None`` when ``transport`` is off, or when the direction declares no
    ``delta`` stream (nothing to quantize — raw/relay directions keep
    the exact stage-free trace).

    The returned ``stage(pre, post, ef) -> (post', ef')`` operates on the
    direction's CONCATENATED wire slab — ``(rows,
    schema.width_aligned(direction))`` — and applies, per stream slice:
    ``delta`` → the quantize→dequantize→EF fold of :func:`make_stage`;
    ``raw``/``relay`` → pass-through (their EF slice stays zero). Chunk
    divisibility is validated HERE, at stage construction, with an error
    naming the strategy and widths — not as a cryptic reshape failure
    deep inside the jitted round.
    """
    if transport is None:
        return None
    _check_transport(transport)
    streams = schema.streams(direction)
    chunk = int(transport.chunk)
    for s in streams:
        if s.coding == "delta" and s.width_aligned % chunk:
            raise ValueError(
                f"TransportConfig.chunk={chunk} does not divide the "
                f"{schema.strategy!r} {direction} stream {s.name!r}: "
                f"width {s.width} aligns to a {s.width_aligned}-wide slab "
                f"slice ({schema.strategy} {direction} wire is "
                f"{schema.width_aligned(direction)} wide) — pick a chunk "
                "dividing the aligned stream width (128 always does)"
            )
    if not any(s.coding == "delta" for s in streams):
        return None
    slices = schema.slices(direction)

    def per_stream(pre, post, ef):
        outs, efs = [], []
        for s, (lo, hi) in zip(streams, slices):
            p, q, e = pre[..., lo:hi], post[..., lo:hi], ef[..., lo:hi]
            if s.coding == "delta" and hi > lo:
                carry = (q - p) + e
                deq = roundtrip(carry, transport)
                outs.append(p + deq)
                efs.append(carry - deq)
            else:
                outs.append(q)
                efs.append(jnp.zeros_like(e))
        return jnp.concatenate(outs, axis=-1), jnp.concatenate(efs, axis=-1)

    # the single-stream stage IS make_stage (no concat in the trace):
    # every pre-schema single-delta trajectory stays bit-identical
    inner = make_stage(transport) if len(streams) == 1 else per_stream

    def stage(pre, post, ef):
        with spans.scope(spans.WIRE):
            return inner(pre, post, ef)

    return stage
