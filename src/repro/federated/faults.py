"""Deterministic fault injection + graceful degradation for cohort rounds.

Fixed-shape injection contract
------------------------------
Every fault is expressed as a masked transform of the quantities the
jitted cohort round ALREADY carries — the replicated (c, d) upload slab
``post_flat`` (the cohort's raveled post-SGD models), its pre-SGD
counterpart ``pre_flat``, and the padded cohort's ``(idx, mask)`` slot
arrays. Nothing changes shape, no host sync happens in-round, and the
whole stage rides inside the ONE compiled round per policy:

  * Byzantine corruption (``attack`` ∈ ``sign_flip`` / ``scaled_noise``
    / ``nan`` / ``inf``) rewrites the attacker slots' rows of
    ``post_flat`` in place — a static attacker set drawn once from
    ``seed`` (:func:`attacker_mask`), so the same clients lie every
    round, like a real compromised population;
  * mid-round upload drops flip a slot to a masked PAD slot after local
    SGD: ``mask`` goes False and ``idx`` becomes the sentinel ``m``, so
    the drop exercises the exact sentinel-drop contract the scatter and
    every masked (c, c) rule were built on — the dropped client keeps
    its previous model and contributes zero mix weight;
  * straggler timeouts are a PRICING fault: ``deadline`` feeds
    :func:`repro.core.comm_model.deadline_round_time`, which censors
    compute times and returns the dropped-slot mask for replays
    (the device round sees them as drops via ``drop_rate``).

The finite guard (:func:`finite_guard`) is the graceful-degradation
half: non-finite upload rows are demoted to masked pad slots AND zeroed
in the slab (a zero-weight column of NaNs would still poison the fused
mix — ``0 · NaN = NaN``), so the round survives ANY number of poisoned
uploads; with every slot demoted the sentinel-index scatter writes
nothing and the round degrades to skip-round semantics (state
unchanged).

Wire-slab generality: the stage operates on whatever slab the strategy's
:class:`~repro.federated.transport.WireSchema` declares as its uplink —
the single ``(c, d_al)`` delta for most strategies, SCAFFOLD's
concatenated ``(c, 2·d_al)`` model+control wire, ... Every transform
here is shape-agnostic over the trailing axis, and the finite guard
checks finiteness PER STREAM (ANDed across the schema's slices — a NaN
in scaffold's control stream demotes the whole slot, exactly like a NaN
in its model stream: the slot's upload is one wire transmission).

Donation interaction: the stage runs between local SGD and the mix
inside the SAME jitted body, on cohort-shaped intermediates — the
donated (m, ·) state buffers are never touched by the rewrite, so the
engine's ``donate_argnums`` discipline (and
``simulation.donation_safe_copy`` for callers) is unchanged.

Determinism: the attacker set is a pure function of ``(seed, m)``; the
per-round drop/noise randomness derives from the round key via
``fold_in`` plus client-indexed per-slot keys
(:func:`repro.core.baselines.common.cohort_keys` discipline), so padded
cohorts reproduce unpadded ones bit-for-bit and a replay with the same
seeds injects the same faults.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import aggregation
from repro.federated import spans

_FOLD = 0xFA117  # fault key domain separator (never collides with training)


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Opt-in fault model (``FedConfig.faults``).

    Attributes:
      seed: draws the static attacker set (host- and trace-reproducible).
      byzantine_frac: fraction of the m clients that are attackers
        (``round(frac · m)`` of them, fixed for the whole run).
      attack: what an attacker uploads — ``sign_flip`` (the inverted,
        ``attack_scale``-amplified update), ``scaled_noise`` (a random
        Gaussian model of scale ``attack_scale`` around the pre-SGD
        point), ``nan`` / ``inf`` (non-finite garbage; exercises the
        finite guard).
      attack_scale: magnitude knob of sign_flip / scaled_noise.
      drop_rate: per-slot probability a REAL upload is lost mid-round
        (applies to every client, honest or not).
      deadline: straggler compute-time ceiling for §V-D pricing
        (``comm_model.deadline_round_time``); ``inf`` = no timeouts.
    """

    seed: int = 0
    byzantine_frac: float = 0.0
    attack: str = "sign_flip"
    attack_scale: float = 10.0
    drop_rate: float = 0.0
    deadline: float = math.inf

    _ATTACKS = ("sign_flip", "scaled_noise", "nan", "inf")

    def __post_init__(self):
        if self.attack not in self._ATTACKS:
            raise ValueError(f"unknown attack {self.attack!r} (expected one of {self._ATTACKS})")
        if not 0.0 <= self.byzantine_frac <= 1.0:
            raise ValueError(f"byzantine_frac must be in [0, 1], got {self.byzantine_frac}")
        if not 0.0 <= self.drop_rate <= 1.0:
            raise ValueError(f"drop_rate must be in [0, 1], got {self.drop_rate}")


def num_attackers(cfg: FaultConfig, m: int) -> int:
    return int(round(cfg.byzantine_frac * m))


def attacker_mask(cfg: FaultConfig, m: int):
    """The static (m,) bool attacker set — a pure function of (seed, m).

    Usable both inside jit (m is a static shape) and host-side (the
    Byzantine replay needs the same set to score W quarantine mass).
    """
    k = num_attackers(cfg, m)
    out = jnp.zeros((m,), bool)
    if k == 0:
        return out
    perm = jax.random.permutation(jax.random.PRNGKey(cfg.seed), m)
    return out.at[perm[:k]].set(True)


def inject(cfg: FaultConfig, pre_flat, post_flat, idx, mask, key, m: int):
    """Apply the round's faults to the upload stage.

    Args:
      pre_flat / post_flat: (c, d) raveled cohort params before/after
        local SGD (the same pair the W refresh consumes).
      idx / mask: the padded cohort slot arrays.
      key: the ROUND key — folded into the fault domain here, so the
        training key stream is untouched (faults off stays bit-exact).
      m: static client count (sentinel value for drops).
    Returns:
      ``(post_flat', idx', mask')``.
    """
    safe = aggregation.safe_gather_index(idx, m)
    fkey = jax.random.fold_in(key, _FOLD)
    # client-indexed per-slot keys: a slot's faults depend only on its
    # client id and the round, not on cohort composition/padding
    slot_keys = jnp.take(jax.random.split(fkey, m), safe, axis=0)

    if cfg.byzantine_frac > 0.0:
        atk = jnp.take(attacker_mask(cfg, m), safe) & mask
        if cfg.attack == "sign_flip":
            bad = pre_flat - cfg.attack_scale * (post_flat - pre_flat)
        elif cfg.attack == "scaled_noise":

            def _noise(k, r):
                return cfg.attack_scale * jax.random.normal(jax.random.fold_in(k, 1), r.shape)

            bad = pre_flat + jax.vmap(_noise)(slot_keys, post_flat)
        elif cfg.attack == "nan":
            bad = jnp.full_like(post_flat, jnp.nan)
        else:  # inf
            bad = jnp.full_like(post_flat, jnp.inf)
        post_flat = jnp.where(atk[:, None], bad, post_flat)

    if cfg.drop_rate > 0.0:
        u = jax.vmap(lambda k: jax.random.uniform(jax.random.fold_in(k, 2)))(slot_keys)
        drop = (u < cfg.drop_rate) & mask
        mask = mask & ~drop
        idx = jnp.where(drop, m, idx)
    return post_flat, idx, mask


def finite_guard(flat_c, idx, mask, m: int, schema=None):
    """Demote non-finite upload rows to masked pad slots.

    A guarded row gets mask False, the sentinel index ``m`` (so the
    fused scatter drops it — the client keeps its previous model) and a
    ZEROED slab row: the masked rules only zero a bad column's WEIGHT,
    and ``0 · NaN = NaN`` would still poison the mix. With every row
    demoted the round degrades to skip-round semantics. Returns
    ``(flat_c', idx', mask')``.

    ``schema`` (the strategy's wire schema) checks finiteness per uplink
    STREAM slice and ANDs the flags — numerically identical to the
    whole-row check (booleans associate), but it states the contract the
    multi-stream wire needs: ANY stream of a slot's upload going
    non-finite demotes the whole slot.
    """
    if schema is None:
        finite = jnp.all(jnp.isfinite(flat_c), axis=-1)
    else:
        finite = jnp.ones(flat_c.shape[:-1], bool)
        for lo, hi in schema.slices("uplink"):
            finite &= jnp.all(jnp.isfinite(flat_c[..., lo:hi]), axis=-1)
    finite = finite & mask
    return (
        jnp.where(finite[:, None], flat_c, 0.0),
        jnp.where(finite, idx, m),
        finite,
    )


def upload_stage(faults_cfg: FaultConfig | None, robust_cfg=None, schema=None):
    """Compose inject → finite guard → robust rewrite into ONE stage.

    Returns ``None`` when both knobs are off (the round body keeps its
    exact pre-existing trace — bit-exact), else a traceable
    ``stage(pre_flat, post_flat, idx, mask, key, m) ->
    (post_flat', idx', mask')`` the round bodies thread between local
    SGD and the masked mix. ``pre_flat``/``post_flat`` are the
    strategy's concatenated uplink WIRE slab (``schema`` — the single
    delta for most strategies); injection, guard and robust rules are
    all shape-agnostic over its width, and the guard demotes per stream
    (see :func:`finite_guard`). The finite guard runs whenever the stage
    is active: robustness without graceful degradation would still die
    on the first NaN upload, and fault injection without it is the
    non-survival baseline the subsystem exists to remove.
    """
    rstage = aggregation.robust_stage(robust_cfg)
    if faults_cfg is None and rstage is None:
        return None

    def stage(pre_flat, post_flat, idx, mask, key, m):
        with spans.scope(spans.UPLOAD):
            if faults_cfg is not None:
                post_flat, idx, mask = inject(faults_cfg, pre_flat, post_flat, idx, mask, key, m)
            post_flat, idx, mask = finite_guard(post_flat, idx, mask, m, schema)
            if rstage is not None:
                post_flat, idx, mask = rstage(post_flat, idx, mask, m)
            return post_flat, idx, mask

    return stage
