"""Config matrix and superstep lowering for the invariant analyzer.

The analyzer never runs training: every lowered pass works on the traced
jaxpr (and optionally the compiled executable) of a superstep built for
one :class:`SuperstepSpec` — a point in the mode × codec × telemetry ×
participation × controller × ef_store × sharding matrix.  This module
owns that construction so the passes, the CLI and the tests all lower
the exact program the engine would jit, with the exact donations
(:func:`repro.engine.superstep.donation_argnums`) and the exact abstract
argument layout (:func:`repro.engine.superstep.abstract_superstep_args`).

The fixture is deliberately tiny (the tests' 8×8 CNN, 8 clients) —
invariants like "one psum per round body" are shape-independent, and a
small model keeps tracing the full matrix cheap enough for CI.
"""
from __future__ import annotations

import dataclasses
import math
import warnings as _warnings
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax

from repro.analysis.registry import AnalysisFailure
from repro.compress import make_codec
from repro.control import (LadderSpec, ladder_kind, ladder_values,
                           make_controller)
from repro.core.rounds import init_global_state
from repro.engine.sharded import client_sharding, make_sharded_superstep
from repro.engine.superstep import (abstract_superstep_args,
                                    donation_argnums,
                                    make_compressed_superstep,
                                    make_plain_superstep)
from repro.launch.mesh import make_engine_mesh
from repro.obs.telemetry import make_telemetry

# Fixture federation: mirrors tests/test_engine.py so every pinned count
# in the subprocess invariant tests and every analyzer expectation talk
# about the same traced program family.
N_CLIENTS = 8
CLIENTS_PER_ROUND = 4
INPUT_SHAPE = (8, 8, 1)

# Codec cases (fl overrides per case), the same axis the engine tests
# sweep: identity wire, stateful top-k EF, stateless int8, asymmetric
# int8-up/topk-down, and the fedfusion algorithm on a top-k wire.
CODEC_CASES = {
    "plain": dict(),
    "topk": dict(uplink_codec="topk", topk_frac=0.1),
    "int8": dict(uplink_codec="int8"),
    "quant+downtopk": dict(uplink_codec="int8", downlink_codec="topk",
                           topk_frac=0.1),
    "fusion-topk": dict(algorithm="fedfusion", fusion_op="conv",
                        uplink_codec="topk", topk_frac=0.1),
}

_BUNDLE = None


def analysis_bundle():
    """The analyzer's model fixture: the tests' tiny 8×8 CNN."""
    global _BUNDLE
    if _BUNDLE is None:
        from repro.configs import CNN_CONFIGS
        from repro.models.registry import make_bundle
        cfg = dataclasses.replace(CNN_CONFIGS["cnn_mnist"],
                                  input_shape=INPUT_SHAPE,
                                  conv_channels=(4,), fc_units=(8,),
                                  dropout=0.0)
        _BUNDLE = make_bundle(cfg)
    return _BUNDLE


@dataclass(frozen=True)
class SuperstepSpec:
    """One point of the analysis matrix.

    ``codec`` keys :data:`CODEC_CASES`; ``controller`` is a
    ``repro.control`` registry name (``"static"`` = off); ``fused`` only
    matters when ``sharded`` (the unsharded superstep has no collectives
    at all); ``ef_store="host"`` lowers against the cohort-paged EF page
    layout instead of the dense/resident table.
    """
    mode: str = "client_parallel"
    codec: str = "plain"
    sharded: bool = False
    fused: bool = True
    telemetry: bool = False
    participation: bool = False
    controller: str = "static"
    ef_store: str = "device"
    n_rounds: int = 4

    @property
    def compressed(self) -> bool:
        return bool(CODEC_CASES[self.codec])

    @property
    def point(self) -> str:
        """Stable id for reports/findings."""
        bits = [self.mode, self.codec,
                ("fused" if self.fused else "unfused") if self.sharded
                else "unsharded"]
        if self.telemetry:
            bits.append("tele")
        if self.participation:
            bits.append("part")
        if self.controller != "static":
            bits.append(f"ctrl={self.controller}")
        if self.ef_store != "device":
            bits.append(f"ef={self.ef_store}")
        return "/".join(bits)


def fl_for(spec: SuperstepSpec):
    """The :class:`FLConfig` the engine would run at this matrix point."""
    from repro.configs.base import FLConfig
    kw = dict(CODEC_CASES[spec.codec])
    algo = kw.pop("algorithm", "fedavg")
    if spec.participation:
        kw.update(participation="deadline", over_provision=1.5)
    if spec.controller != "static":
        kw.update(controller=spec.controller)
    return FLConfig(algorithm=algo, clients_per_round=CLIENTS_PER_ROUND,
                    local_steps=2, local_batch=4, lr=0.05, **kw)


@dataclass
class LoweredSuperstep:
    """A superstep traced (and lazily compiled) at one matrix point.

    ``fn`` is the pre-jit callable (already ``shard_map``-wrapped when
    sharded), ``args`` the abstract argument tuple, ``jaxpr`` the closed
    jaxpr of ``fn(*args)``.  ``compiled_text`` compiles with the
    engine's donations and returns the optimized HLO module text (what
    ``repro.roofline.hlo`` parses); compile-time warnings — XLA's
    "donated buffer was not usable" in particular — are captured into
    ``compile_warnings``.
    """
    spec: SuperstepSpec
    fl: object
    fn: object
    args: Tuple
    donate_argnums: Tuple[int, ...]
    cohort: int
    ef_rows: Optional[int] = None
    uplink: object = None
    downlink: object = None
    controller: object = None
    mesh: object = None
    wire_up: Optional[int] = None
    wire_down: Optional[int] = None
    level_bytes: Optional[Tuple[int, ...]] = None
    _jaxpr: object = field(default=None, repr=False)
    _hlo: Optional[str] = field(default=None, repr=False)
    compile_warnings: List[str] = field(default_factory=list, repr=False)

    @property
    def point(self) -> str:
        return self.spec.point

    @property
    def jaxpr(self):
        if self._jaxpr is None:
            self._jaxpr = jax.make_jaxpr(self.fn)(*self.args)
        return self._jaxpr

    @property
    def compiled_text(self) -> str:
        if self._hlo is None:
            with _warnings.catch_warnings(record=True) as caught:
                _warnings.simplefilter("always")
                compiled = jax.jit(
                    self.fn, donate_argnums=self.donate_argnums
                ).lower(*self.args).compile()
            self.compile_warnings = [str(w.message) for w in caught]
            self._hlo = compiled.as_text()
        return self._hlo

    @property
    def ideal_model_bytes(self) -> int:
        """Uncompressed f32 wire bytes of one model delta (the CommLog
        'ideal' baseline every codec's wire bytes are charged against)."""
        state = self.args[0]
        total = 0
        for leaf in jax.tree.leaves(state["model"]):
            total += math.prod(leaf.shape) * 4
        return total


def _ef_rows(spec: SuperstepSpec, cohort: int, n_shards: int) -> int:
    """Leading row count of the EF table argument for this layout."""
    K = spec.n_rounds
    if spec.ef_store == "host":        # cohort-paged: one page per chunk
        page = K * cohort
        return (page + 1) * n_shards if spec.sharded else page
    if spec.sharded:                    # resident scratch-row layout
        return (N_CLIENTS // n_shards + 1) * n_shards
    return N_CLIENTS                    # dense table


def lower_superstep(spec: SuperstepSpec, *, inner_wrap=None,
                    donate="engine") -> LoweredSuperstep:
    """Build + abstractly trace the superstep at one matrix point.

    ``inner_wrap`` threads through to
    :func:`repro.engine.sharded.make_sharded_superstep` (sharded) or
    wraps the superstep directly (unsharded) — the mutation tests use it
    to seed violations.  ``donate="engine"`` uses the engine's
    :func:`donation_argnums`; pass ``()`` to lower without donation
    (how the donation pass seeds its own violation).
    """
    if spec.codec not in CODEC_CASES:
        raise AnalysisFailure(f"unknown codec case {spec.codec!r}; have "
                              f"{tuple(sorted(CODEC_CASES))}")
    bundle = analysis_bundle()
    fl = fl_for(spec)
    compressed = spec.compressed
    ctrl_active = compressed and spec.controller != "static"

    mesh = shard = None
    n_shards = 1
    if spec.sharded:
        if jax.device_count() < 2:
            raise AnalysisFailure(
                "sharded analysis points need >= 2 devices; relaunch "
                "under XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "(the repro.analysis CLI does this automatically)")
        mesh = make_engine_mesh()
        shard = client_sharding(mesh)
        n_shards = shard.n_shards

    from repro.fl.participation import make_policy
    cohort = CLIENTS_PER_ROUND
    if spec.participation:
        cohort = make_policy(fl.participation).cohort_size(
            CLIENTS_PER_ROUND, fl)
    if spec.sharded and cohort % n_shards:
        raise AnalysisFailure(f"cohort {cohort} does not divide over "
                              f"{n_shards} shards at {spec.point}")

    uplink = downlink = controller = None
    ef_rows = wire_up = wire_down = level_bytes = None
    if compressed:
        uplink = make_codec(fl.uplink_codec, topk_frac=fl.topk_frac,
                            quant_bits=fl.quant_bits)
        downlink = make_codec(fl.downlink_codec, topk_frac=fl.topk_frac,
                              quant_bits=fl.quant_bits)
        state = jax.eval_shape(lambda k: init_global_state(bundle, fl, k),
                               jax.random.PRNGKey(0))
        uplink.bind(state["model"])
        downlink.bind(state["model"])
        wire_up = uplink.wire_bytes()
        wire_down = downlink.wire_bytes()
        if ctrl_active:
            ladder = ladder_values(fl)
            uplink.set_ladder(ladder)
            level_bytes = tuple(uplink.level_bytes())
            ctrl_spec = LadderSpec(kind=ladder_kind(fl.uplink_codec),
                                   values=ladder, bytes_up=level_bytes)
            controller = make_controller(spec.controller).setup(ctrl_spec,
                                                                fl)
        ef_rows = _ef_rows(spec, cohort, n_shards)

    tele = None
    if spec.telemetry or ctrl_active:
        tele = make_telemetry(
            "compressed" if compressed else "plain", n_clients=cohort,
            n_shards=n_shards,
            available=frozenset(
                (("ef",) if compressed and uplink.stateful else ())
                + (("pmask", "staleness") if spec.participation else ())
                + (("level", "eff_bytes") if ctrl_active else ())))
        if ctrl_active:
            have = {t.name for t in tele.taps}
            missing = [n for n in controller.requires_taps
                       if n not in have]
            if missing:
                raise AnalysisFailure(
                    f"controller {spec.controller!r} needs taps {missing} "
                    f"unavailable for codec {spec.codec!r} at {spec.point}")

    if spec.sharded:
        fn = make_sharded_superstep(
            bundle, fl, spec.mode, spec.n_rounds, mesh, uplink=uplink,
            downlink=downlink, fused_collective=spec.fused, telemetry=tele,
            participation=spec.participation, controller=controller,
            inner_wrap=inner_wrap)
    else:
        if compressed:
            fn = make_compressed_superstep(
                bundle, fl, spec.mode, spec.n_rounds, uplink, downlink,
                telemetry=tele, participation=spec.participation,
                controller=controller)
        else:
            fn = make_plain_superstep(
                bundle, fl, spec.mode, spec.n_rounds, telemetry=tele,
                participation=spec.participation)
        if inner_wrap is not None:
            fn = inner_wrap(fn)

    args = abstract_superstep_args(
        bundle, fl, spec.n_rounds, cohort=cohort, uplink=uplink,
        ef_rows=ef_rows, participation=spec.participation,
        controller=controller, input_shape=INPUT_SHAPE)

    if donate == "engine":
        donate = donation_argnums(
            compressed=compressed, participation=spec.participation,
            controller=ctrl_active)
    return LoweredSuperstep(
        spec=spec, fl=fl, fn=fn, args=args, donate_argnums=tuple(donate),
        cohort=cohort, ef_rows=ef_rows, uplink=uplink, downlink=downlink,
        controller=controller, mesh=mesh, wire_up=wire_up,
        wire_down=wire_down, level_bytes=level_bytes)


def default_matrix(preset: str = "quick", *,
                   sharded: Optional[bool] = None) -> List[SuperstepSpec]:
    """The analyzer's config matrix.

    A covering design, not a full cross-product: a base mode × codec
    grid with everything else off, one point per extra feature
    (telemetry / participation / each controller / paged EF store), and
    everything-on points — ~12 specs for ``"quick"``, ~30 for
    ``"full"``.  ``sharded`` filters: True keeps only sharded points
    (what the CLI runs in its forced-device subprocess), False only
    unsharded ones.
    """
    if preset not in ("quick", "full"):
        raise AnalysisFailure(f"unknown preset {preset!r}")
    S = SuperstepSpec
    specs: List[SuperstepSpec] = []
    # base grid: every codec unsharded, plus the sharded fused points
    for codec in CODEC_CASES:
        specs.append(S(codec=codec))
        specs.append(S(codec=codec, sharded=True))
    # the three-collective oracle layout
    specs.append(S(codec="topk", sharded=True, fused=False))
    # single-feature points on the stateful-EF wire
    specs.append(S(codec="topk", telemetry=True))
    specs.append(S(codec="topk", sharded=True, telemetry=True))
    specs.append(S(codec="topk", sharded=True, participation=True))
    specs.append(S(codec="topk", sharded=True, controller="ef_ratio"))
    specs.append(S(codec="topk", ef_store="host"))
    specs.append(S(codec="topk", sharded=True, ef_store="host"))
    # everything on
    specs.append(S(codec="topk", sharded=True, telemetry=True,
                   participation=True, controller="ef_ratio",
                   ef_store="host"))
    if preset == "full":
        specs.append(S(mode="client_sequential", codec="topk"))
        specs.append(S(mode="client_sequential", codec="topk",
                       sharded=True))
        specs.append(S(codec="plain", sharded=True, fused=False))
        specs.append(S(codec="quant+downtopk", sharded=True, fused=False))
        specs.append(S(codec="fusion-topk", sharded=True, fused=False))
        specs.append(S(codec="topk", participation=True))
        specs.append(S(codec="topk", controller="ef_ratio"))
        specs.append(S(codec="topk", controller="bytes_budget"))
        specs.append(S(codec="topk", controller="loss_trend"))
        specs.append(S(codec="topk", sharded=True,
                       controller="bytes_budget"))
        specs.append(S(codec="topk", sharded=True,
                       controller="loss_trend"))
        specs.append(S(codec="quant+downtopk", sharded=True,
                       telemetry=True))
        specs.append(S(codec="topk", sharded=True, fused=False,
                       telemetry=True, participation=True,
                       controller="ef_ratio"))
        specs.append(S(codec="topk", telemetry=True, participation=True,
                       controller="ef_ratio", ef_store="host"))
    if sharded is not None:
        specs = [s for s in specs if s.sharded == sharded]
    return specs
