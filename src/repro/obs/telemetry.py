"""Zero-sync on-device telemetry taps for the federated round functions.

The signals the adaptive-compression and capacity-planning roadmap items
need — delta norms before/after the wire codec, EF residual mass, the
residual/delta ratio, compression error, the round's example total — are
all computed on device every round and then thrown away.  This module
turns them into *taps*: small traceable hooks the round factories in
``repro.core.rounds`` evaluate alongside training, whose outputs ride the
EXISTING stacked-``[K]`` metrics path through the superstep scan and the
``MetricsPump``.  Telemetry therefore costs

* **zero extra host syncs** — tap values land in the same deferred
  metrics stack every other per-round metric uses; and
* **zero extra collectives** — per-client tap sums are packed into the
  psum the round already performs (the contribution-sum tree in unfused
  sharded mode, the PR 5 single fused psum in fused mode; ``psum`` of a
  tree is one collective regardless of leaf count, and elementwise
  reduction means the pre-existing leaves keep their exact values, so a
  telemetry-on run stays bitwise-equal to telemetry-off).

Tap protocol (registered like ``Algorithm`` / ``make_codec`` plugins):

* ``client_sums(ctx)`` runs once per client inside the round's
  vmap/scan and returns a flat ``{key: f32 scalar}`` dict of
  *psum-pending sums* — summed over the round's clients (and shards)
  before finalization.  Keys are namespaced ``"{tap.name}.{key}"``.
* ``finish(summed, ctx)`` runs replicated after the sums complete and
  maps them to the emitted metrics (prefix ``tele/``) — ratios and
  normalizations belong here, never in ``client_sums`` (a quotient does
  not sum).

``kinds`` declares which round flavours a tap understands
(``"plain"`` / ``"compressed"``) and ``requires`` which
:class:`ClientTapCtx` fields it reads, so :func:`make_telemetry` only
activates taps whose inputs exist (the EF tap needs a stateful uplink).

Everything is f32 end to end: tap sums ride the engine's fused psum
buffer, which is single-dtype by contract.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, FrozenSet, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import AGGREGATE, in_part

__all__ = ["ClientTapCtx", "RoundTapCtx", "TelemetryTap", "Telemetry",
           "register_tap", "registered_taps", "make_telemetry",
           "TELEMETRY_PREFIX"]

TELEMETRY_PREFIX = "tele/"

# guards the residual/delta ratio against a zero-delta round; f32 tiny
_EPS = 1e-20


def _sq_sum(tree) -> jnp.ndarray:
    """Σ x² over every leaf of a pytree, as one f32 scalar."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)


@dataclass(frozen=True)
class ClientTapCtx:
    """What one client's round computation exposes to ``client_sums``.

    Fields are None when the round flavour does not produce them; a tap
    lists the ones it reads in ``requires`` and is skipped when any is
    unavailable.  All trees are this client's (un-vmapped) values.
    """

    n_examples: Any = None      # scalar — this client's example count
    loss: Any = None            # scalar — local training loss
    model: Any = None           # tree — trained local trainable (plain)
    global_model: Any = None    # tree — the model clients started from
    delta: Any = None           # tree — PRE-compression update (compressed)
    decoded: Any = None         # tree — POST-compression decoded update
    ef: Any = None              # tree — the client's NEW EF residual
    pmask: Any = None           # scalar — 0/1 participation mask
    staleness: Any = None       # scalar — rounds late (participation)
    level: Any = None           # scalar — effective ladder level (control)
    eff_bytes: Any = None       # scalar — effective uplink payload bytes


@dataclass(frozen=True)
class RoundTapCtx:
    """Round-level statics available to ``finish`` (no traced values)."""

    n_clients: int = 1          # C — the FULL round's sampled clients
    n_shards: int = 1           # client shards the round runs across


class TelemetryTap:
    """Base tap: subclass, set ``name``/``kinds``/``requires``, implement
    the two hooks.  Stateless by contract — one instance serves every
    round fn build."""

    name: str = "?"
    kinds: Tuple[str, ...] = ("plain", "compressed")
    requires: Tuple[str, ...] = ()

    def client_sums(self, ctx: ClientTapCtx) -> Dict[str, jnp.ndarray]:
        return {}

    def finish(self, summed: Dict[str, jnp.ndarray],
               ctx: RoundTapCtx) -> Dict[str, jnp.ndarray]:
        return {}


class DeltaNormTap(TelemetryTap):
    """RMS per-client update norm before and after the uplink codec, plus
    the compression error between them — the compression controller's
    primary signal (CFedAvg retunes on exactly this)."""

    name = "delta"
    kinds = ("compressed",)
    requires = ("delta", "decoded")

    def client_sums(self, ctx):
        return {"pre_sq": _sq_sum(ctx.delta),
                "post_sq": _sq_sum(ctx.decoded),
                "err_sq": _sq_sum(jax.tree.map(
                    lambda a, b: a.astype(jnp.float32)
                    - b.astype(jnp.float32), ctx.delta, ctx.decoded))}

    def finish(self, summed, ctx):
        c = jnp.float32(ctx.n_clients)
        return {"delta_norm_pre": jnp.sqrt(summed["delta.pre_sq"] / c),
                "delta_norm_post": jnp.sqrt(summed["delta.post_sq"] / c),
                "compress_err": jnp.sqrt(summed["delta.err_sq"] / c)}


class EFResidualTap(TelemetryTap):
    """RMS error-feedback residual norm and the residual/delta mass
    ratio: how much update the codec is deferring round over round.  A
    ratio trending up means the codec is too aggressive for the current
    delta distribution — the retuning signal ROADMAP item 4 names."""

    name = "ef"
    kinds = ("compressed",)
    requires = ("ef", "delta")

    def client_sums(self, ctx):
        # carries its own delta mass so the tap works standalone (taps
        # must not read each other's sums — selection is per-tap)
        return {"sq": _sq_sum(ctx.ef), "delta_sq": _sq_sum(ctx.delta)}

    def finish(self, summed, ctx):
        c = jnp.float32(ctx.n_clients)
        return {"ef_norm": jnp.sqrt(summed["ef.sq"] / c),
                "ef_delta_ratio": jnp.sqrt(
                    summed["ef.sq"]
                    / jnp.maximum(summed["ef.delta_sq"], _EPS))}


class UpdateNormTap(TelemetryTap):
    """RMS per-client drift of the trained local model from the global
    one (the uncompressed round's analogue of the delta norm)."""

    name = "update"
    kinds = ("plain",)
    requires = ("model", "global_model")

    def client_sums(self, ctx):
        return {"sq": _sq_sum(jax.tree.map(
            lambda a, g: a.astype(jnp.float32) - g.astype(jnp.float32),
            ctx.model, ctx.global_model))}

    def finish(self, summed, ctx):
        return {"update_norm": jnp.sqrt(
            summed["update.sq"] / jnp.float32(ctx.n_clients))}


class WeightTap(TelemetryTap):
    """The round's aggregate example total (the FedAvg normalizer) and
    the per-shard client count — the per-host balance signals the pod
    launch (ROADMAP item 1) needs."""

    name = "weights"
    kinds = ("plain", "compressed")
    requires = ("n_examples",)

    def client_sums(self, ctx):
        return {"total": jnp.asarray(ctx.n_examples, jnp.float32)}

    def finish(self, summed, ctx):
        return {"weight_total": summed["weights.total"],
                "clients": jnp.float32(ctx.n_clients),
                "clients_per_shard": jnp.float32(
                    ctx.n_clients // max(ctx.n_shards, 1))}


class ParticipationTap(TelemetryTap):
    """Partial-cohort health: how many of the sampled lanes contributed,
    how many were dropped/late out of the round, and the mean staleness
    of the contributions that did land (buffered-async discounting).
    Active only when the participation axis is on (the engine adds
    ``pmask``/``staleness`` to ``available``), so full-sync/chaos-off
    builds stay byte-identical."""

    name = "participation"
    kinds = ("plain", "compressed")
    requires = ("pmask", "staleness")

    def client_sums(self, ctx):
        m = jnp.asarray(ctx.pmask, jnp.float32)
        return {"arrived": m,
                "stale_sum": jnp.asarray(ctx.staleness, jnp.float32) * m}

    def finish(self, summed, ctx):
        arrived = summed["participation.arrived"]
        return {"effective_cohort": arrived,
                "dropped_clients": jnp.float32(ctx.n_clients) - arrived,
                "mean_staleness": summed["participation.stale_sum"]
                / jnp.maximum(arrived, 1.0)}


class ControllerTap(TelemetryTap):
    """The adaptive-compression schedule (repro.control): the round's
    effective ladder level and per-client effective uplink payload bytes.
    Every client of a round encodes at the SAME level, so the psum-mean
    is exact regardless of participation masking.  Active only when a
    controller is on (the engine adds ``level``/``eff_bytes`` to
    ``available``), so static builds stay byte-identical."""

    name = "controller"
    kinds = ("compressed",)
    requires = ("level", "eff_bytes")

    def client_sums(self, ctx):
        return {"level": jnp.asarray(ctx.level, jnp.float32),
                "bytes": jnp.asarray(ctx.eff_bytes, jnp.float32)}

    def finish(self, summed, ctx):
        c = jnp.float32(ctx.n_clients)
        return {"level": summed["controller.level"] / c,
                "effective_bytes": summed["controller.bytes"] / c}


_TAPS: Dict[str, TelemetryTap] = {}


def register_tap(tap: TelemetryTap) -> TelemetryTap:
    """Add a tap to the registry (codec/algorithm plugins call this the
    same way they call ``register_algorithm``); re-registering a name
    replaces it."""
    if not tap.name or tap.name == "?":
        raise ValueError("telemetry taps need a non-default name")
    _TAPS[tap.name] = tap
    return tap


def registered_taps() -> Tuple[str, ...]:
    return tuple(sorted(_TAPS))


for _t in (DeltaNormTap(), EFResidualTap(), UpdateNormTap(), WeightTap(),
           ParticipationTap(), ControllerTap()):
    register_tap(_t)


@dataclass(frozen=True)
class Telemetry:
    """The taps active for one round-fn build, pre-filtered by kind and
    input availability; what the round factories actually consume."""

    taps: Tuple[TelemetryTap, ...]
    round_ctx: RoundTapCtx = field(default_factory=RoundTapCtx)

    @in_part(AGGREGATE)
    def client_sums(self, ctx: ClientTapCtx) -> Dict[str, jnp.ndarray]:
        """Flat namespaced psum-pending sums for one client."""
        out: Dict[str, jnp.ndarray] = {}
        for tap in self.taps:
            for k, v in tap.client_sums(ctx).items():
                out[f"{tap.name}.{k}"] = jnp.asarray(v, jnp.float32)
        return out

    @in_part(AGGREGATE)
    def finish(self, summed: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
        """Summed (psum-completed) tap values -> emitted ``tele/`` metrics."""
        out: Dict[str, Any] = {}
        for tap in self.taps:
            for k, v in tap.finish(summed, self.round_ctx).items():
                out[TELEMETRY_PREFIX + k] = v
        return out


def make_telemetry(kind: str, *, n_clients: int = 1, n_shards: int = 1,
                   available: FrozenSet[str] = frozenset(),
                   taps: Optional[Sequence[str]] = None
                   ) -> Optional[Telemetry]:
    """Build the :class:`Telemetry` for one round-fn flavour.

    ``kind`` is ``"plain"`` or ``"compressed"``; ``available`` names the
    optional :class:`ClientTapCtx` fields the round will populate beyond
    the always-present ``n_examples``/``loss`` (the engine passes
    ``{"ef"}`` only for stateful uplinks).  ``taps=None`` takes every
    registered tap that fits; an explicit name list selects (and
    validates) a subset.  Returns None when nothing applies — callers
    treat that exactly like telemetry-off.
    """
    if kind not in ("plain", "compressed"):
        raise ValueError(f"telemetry kind {kind!r} must be 'plain' or "
                         "'compressed'")
    base = {"n_examples", "loss"}
    base |= ({"model", "global_model"} if kind == "plain"
             else {"delta", "decoded", "global_model"})
    have = base | set(available)
    if taps is None:
        names = registered_taps()
    else:
        unknown = set(taps) - set(_TAPS)
        if unknown:
            raise KeyError(f"unknown telemetry taps {sorted(unknown)}; "
                           f"registered: {registered_taps()}")
        names = tuple(taps)
    chosen = tuple(
        _TAPS[n] for n in names
        if kind in _TAPS[n].kinds and set(_TAPS[n].requires) <= have)
    if not chosen:
        return None
    return Telemetry(taps=chosen,
                     round_ctx=RoundTapCtx(n_clients=n_clients,
                                           n_shards=n_shards))
