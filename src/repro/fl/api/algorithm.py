"""The ``Algorithm`` plugin interface + registry.

The paper's whole contribution is *mechanisms added to on-device
training*; this module makes those mechanisms pluggable the same way
``repro.compress`` makes wire codecs pluggable.  An :class:`Algorithm`
supplies four hooks, each mapping onto one place the federated machinery
used to branch on ``fl.algorithm ==``:

    init_extra_state    global-state entries beyond "model"
                        (FedFusion's fusion module params)
    local_loss          the client's two-stream training objective
                        (FedMMD's MMD constraint, FedProx's prox term)
    aggregate_extras /  server-side aggregation of the extra state
    finalize_extra_sums (fusion-gate EMA through ``ClientSharding`` psums;
                        the *_sums variant closes the client_sequential
                        running-sum path)
    deploy_logits       eval-time logits of the deployed global model
                        (FedFusion fuses the global features with
                        themselves through the aggregated module)

Plugins are stateless singletons registered by name — everything
configurable arrives through the :class:`repro.configs.base.FLConfig`
that every hook receives — so one instance serves any number of
concurrent runs, exactly like codec objects.

The hooks are jax-traceable: ``local_loss``/``aggregate_extras``/
``finalize_extra_sums``/``deploy_logits`` run under jit/vmap/shard_map
inside the round and eval functions, so a plugin must keep its output
pytree *structure* independent of traced values.

Registering a new mechanism (RingFed-style partial averaging, a CFedAvg
variant, ...) never touches ``repro.core``: subclass, implement the
hooks you need, call :func:`register_algorithm` — see
``repro/contrib/fedprox.py`` for a complete out-of-core example.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

__all__ = ["Algorithm", "register_algorithm", "make_algorithm",
           "registered_algorithms"]


class Algorithm:
    """Base algorithm: FedAvg semantics; override hooks to add mechanisms.

    ``name``         registry key (``FLConfig.algorithm``).
    ``two_stream``   True when ``local_loss`` consumes the frozen global
                     stream's features — the local trainer then offers the
                     paper-§3.3 per-round feature cache (``cached_feats_g``).
    ``extra_state``  global-state keys this algorithm carries beyond
                     ``"model"`` (e.g. ``("fusion",)``); the round fns
                     thread/accumulate these generically and hand them
                     back through the aggregation hooks.
    """

    name: str = ""
    two_stream: bool = False
    extra_state: Tuple[str, ...] = ()

    # -- global state ---------------------------------------------------
    def init_extra_state(self, bundle, fl, key) -> Dict[str, Any]:
        """Server line 1 extras: ``{key: params}`` for ``extra_state``."""
        return {}

    def extra_from_state(self, global_state) -> Any:
        """The extra-state value handed to the local trainer (the second
        argument of ``local_train``): the raw params for a single extra
        key, a ``{key: params}`` dict for several, None for none."""
        if not self.extra_state:
            return None
        if len(self.extra_state) == 1:
            return global_state.get(self.extra_state[0])
        return {k: global_state[k] for k in self.extra_state}

    # -- client side ----------------------------------------------------
    def init_trainable(self, fl, global_model, extra) -> Dict[str, Any]:
        """The client's trainable pytree.  Keys must be ``"model"`` plus
        exactly ``extra_state`` — the round fns accumulate/aggregate every
        key generically.  ``extra`` is :meth:`extra_from_state`'s value."""
        return {"model": global_model}

    def local_loss(self, bundle, fl, trainable, global_model, batch,
                   cached_feats_g=None, *, impl="auto"):
        """``(loss, aux_dict)`` for one local SGD step.  ``global_model``
        is the FROZEN global stream (never updated during local training —
        paper Fig. 1); ``cached_feats_g`` carries its precomputed features
        when ``two_stream`` and the trainer cached them (else None)."""
        raise NotImplementedError(self.name)

    # -- server side ----------------------------------------------------
    def aggregate_extras(self, fl, global_state, stacked, weights,
                         shard=None) -> Dict[str, Any]:
        """Aggregate the clients' extra state (client_parallel path).

        ``stacked``: ``{key: pytree with leading client axis}`` for every
        ``extra_state`` key; ``weights [n_clients]`` are globally
        normalized.  Under ``shard`` the client axis holds only this
        shard's clients — complete any cross-client statistic with the
        ``repro.core.aggregate`` psum helpers.

        NOTE: the engine's fused-collective path (the sharded default)
        does not call this hook — it packs the weighted sums of the
        stacked extras into the round's single psum and closes them with
        :meth:`finalize_extra_sums`, so keep the two decompositions
        consistent: ``aggregate_extras(stacked, w) ==
        finalize_extra_sums(psum(client_weighted_sum(w, stacked)))`` (true of every
        in-tree plugin; a plugin needing a different cross-client
        statistic should run with ``fused_collective=False``)."""
        return {}

    def finalize_extra_sums(self, fl, global_state, sums) -> Dict[str, Any]:
        """Close the client_sequential running-sum path: ``sums`` holds
        the psum-completed weighted sums of the clients' extra state."""
        return {}

    # -- deployment -----------------------------------------------------
    def deploy_logits(self, bundle, fl, global_state, out, *, impl="auto"):
        """Logits of the deployed global model given ``out =
        bundle.apply(global_state['model'], batch)`` — the single
        implementation behind jitted eval, the eager oracle and the
        new-client probe."""
        return out["logits"]


# ---------------------------------------------------------------------------
# Registry (mirrors repro.compress.make_codec)
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Algorithm] = {}
_BUILTINS_LOADED = False


def _ensure_builtins() -> None:
    """Idempotently register the in-tree plugin modules."""
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    import repro.fl.api.plugins      # noqa: F401 — registers the paper's four
    import repro.contrib.fedprox     # noqa: F401 — out-of-core demonstration
    # latch only after both imports succeed: a transient ImportError must
    # surface again on the next call, not decay into "unknown algorithm"
    _BUILTINS_LOADED = True


def register_algorithm(algo: Algorithm, *, override: bool = False) -> Algorithm:
    """Register ``algo`` under ``algo.name``; returns it (decorator-friendly
    via ``register_algorithm(MyAlgo())``).  Re-registering an existing name
    requires ``override=True`` so typos can't silently shadow a plugin."""
    if not algo.name:
        raise ValueError("Algorithm.name must be a non-empty string")
    if algo.name in _REGISTRY and not override:
        raise ValueError(f"algorithm {algo.name!r} already registered "
                         f"(pass override=True to replace)")
    _REGISTRY[algo.name] = algo
    return algo


def make_algorithm(name: str) -> Algorithm:
    """Look up an algorithm plugin by config name."""
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown algorithm {name!r}; choose from "
                         f"{registered_algorithms()}") from None


def registered_algorithms() -> Tuple[str, ...]:
    """All registered names, in registration order."""
    _ensure_builtins()
    return tuple(_REGISTRY)
