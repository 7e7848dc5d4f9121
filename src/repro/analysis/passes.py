"""Built-in lowered-superstep invariant passes.

Each pass inspects one :class:`repro.analysis.lower.LoweredSuperstep`
(a config point of the analysis matrix) and returns findings.  The
expectations they pin are the engine's structural contracts:

* ``collectives`` — the fused sharded round body executes exactly ONE
  psum (the paper's one-collective-per-round claim), the whole superstep
  exactly two (prologue + body), the unfused oracle at least the
  three-collective layout, unsharded programs none at all — and nothing
  but psums anywhere;
* ``donation`` — every buffer the engine donates (model state, EF
  table/page, broadcast mirror, lr slice, controller scalars) is
  actually aliased input→output in the compiled executable, with no
  donation-unused warnings and no hidden copy of the EF page;
* ``host-sync`` — no callback / infeed / outfeed primitive anywhere in
  the traced superstep: one host sync per CHUNK is the engine's whole
  performance story;
* ``dtype`` — no f64 (or complex128) value anywhere in the trace, and
  every collective operand is exactly f32: silent x64 promotion through
  a codec would double wire bytes and break the bytes model quietly;
* ``collective-bytes`` — the trip-weighted all-reduce count and payload
  bytes of the optimized HLO (``repro.roofline.hlo``) equal the
  jaxpr-level execution model exactly, and the codec wire model charged
  by the CommLog stays consistent (compressed < ideal f32 bytes, ladder
  monotone with its top rung at the static wire bytes);
* ``scopes`` — every equation of the round body, backward included,
  lies under exactly one of the round's four device scopes
  (``repro.obs.scopes``), apart from vmap's broadcast of an unbatched
  output; the codecs' sort / top-k lies under ``codec`` and every
  convolution under ``local_train`` or ``eval``.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import List

import jax

from repro.analysis.jaxprs import (COLLECTIVE_PRIMITIVES,
                                   HOST_SYNC_PRIMITIVES,
                                   collect_avals,
                                   collective_execution_model,
                                   count_collectives, find_primitives,
                                   leaf_eqns_with_name_stacks, round_body)
from repro.analysis.registry import AnalysisPass, Finding, register_pass
from repro.obs.scopes import PARTS

# jax dtype -> HLO shape-prefix, for matching donated leaves against the
# compiled module's entry parameters
_HLO_DTYPES = {"float32": "f32", "float64": "f64", "float16": "f16",
               "bfloat16": "bf16", "int64": "s64", "int32": "s32",
               "int16": "s16", "int8": "s8", "uint64": "u64",
               "uint32": "u32", "uint16": "u16", "uint8": "u8",
               "bool": "pred"}

# per-device sharding of the superstep arguments: argnum -> the axis the
# client shards split (absent/None = replicated).  Positions follow
# ``abstract_superstep_args``; only donated argnums are ever looked up.
_SHARDED_AXIS_COMPRESSED = {1: 0, 3: 1, 4: 1, 9: 1, 10: 1}
_SHARDED_AXIS_PLAIN = {1: 1, 2: 1, 4: 1, 5: 1}


@register_pass
class CollectivesPass(AnalysisPass):
    name = "collectives"
    scope = "lowered"
    description = ("exactly one psum per fused round body (2 per "
                   "superstep), >= 3 for the unfused oracle, 0 "
                   "unsharded; psum is the only collective flavour")

    def run(self, low) -> List[Finding]:
        out = []
        spec = low.spec
        jx = low.jaxpr
        total = count_collectives(jx)
        psums = count_collectives(jx, names=("psum",))
        if total != psums:
            out.append(self.finding(
                low.point, f"{total - psums} non-psum collective(s) in the "
                f"superstep jaxpr — psum is the only collective the engine "
                f"may emit"))
        if not spec.sharded:
            if total:
                out.append(self.finding(
                    low.point, f"unsharded superstep traced {total} "
                    f"collective(s); a 1-shard program must have none"))
            return out
        body = round_body(jx)
        if body is None:
            out.append(self.finding(
                low.point, "no round scan found in the superstep jaxpr"))
            return out
        n_body = count_collectives(body)
        if spec.fused:
            if n_body != 1:
                out.append(self.finding(
                    low.point, f"fused round body has {n_body} collectives, "
                    f"invariant is exactly 1 (the packed psum)"))
            if total != 2:
                out.append(self.finding(
                    low.point, f"fused superstep has {total} collective "
                    f"equations, invariant is exactly 2 (prologue + round "
                    f"body)"))
        else:
            if n_body < 3:
                out.append(self.finding(
                    low.point, f"unfused round body has {n_body} "
                    f"collectives; the three-collective oracle layout "
                    f"expects >= 3"))
            if total != n_body:
                out.append(self.finding(
                    low.point, f"unfused superstep has {total - n_body} "
                    f"collective(s) outside the round body; the oracle "
                    f"layout keeps every exchange inside the round"))
        return out


@register_pass
class HostSyncPass(AnalysisPass):
    name = "host-sync"
    scope = "lowered"
    description = ("no callback / infeed / outfeed / debug primitive "
                   "anywhere in the traced superstep")

    def run(self, low) -> List[Finding]:
        eqns = find_primitives(low.jaxpr, HOST_SYNC_PRIMITIVES)
        return [self.finding(
            low.point, f"host-synchronizing primitive "
            f"{eqn.primitive.name!r} in the traced superstep — the engine "
            f"syncs with the host once per chunk, never inside the scan")
            for eqn in eqns]


@register_pass
class DtypePass(AnalysisPass):
    name = "dtype"
    scope = "lowered"
    description = ("no f64/complex128 anywhere in the trace; collective "
                   "operands are exactly f32")

    def run(self, low) -> List[Finding]:
        out = []
        seen64 = Counter()
        for aval in collect_avals(low.jaxpr):
            dt = str(getattr(aval, "dtype", ""))
            if dt in ("float64", "complex128"):
                seen64[dt] += 1
        for dt, n in sorted(seen64.items()):
            out.append(self.finding(
                low.point, f"{n} {dt} value(s) in the traced superstep — "
                f"silent x64 promotion (the engine is f32 end to end)"))
        for eqn in find_primitives(low.jaxpr, COLLECTIVE_PRIMITIVES):
            for v in eqn.invars:
                dt = str(getattr(getattr(v, "aval", None), "dtype", ""))
                if dt and dt != "float32":
                    out.append(self.finding(
                        low.point, f"collective {eqn.primitive.name!r} "
                        f"carries a {dt} operand; the packed wire buffer "
                        f"must stay f32"))
        return out


def _expected_aliased_shapes(low) -> Counter:
    """Multiset of per-device ``"dtype[dims]"`` strings the compiled
    module must alias — one per donated argument leaf."""
    spec = low.spec
    n_shards = 1
    if spec.sharded:
        from repro.engine.sharded import client_sharding
        n_shards = client_sharding(low.mesh).n_shards
    axis_of = (_SHARDED_AXIS_COMPRESSED if spec.compressed
               else _SHARDED_AXIS_PLAIN)
    expect = Counter()
    for argnum in low.donate_argnums:
        axis = axis_of.get(argnum)
        for leaf in jax.tree.leaves(low.args[argnum]):
            dims = list(leaf.shape)
            if spec.sharded and axis is not None and dims:
                dims[axis] //= n_shards
            dt = _HLO_DTYPES.get(str(leaf.dtype), str(leaf.dtype))
            expect[f"{dt}[{','.join(str(d) for d in dims)}]"] += 1
    return expect


@register_pass
class DonationPass(AnalysisPass):
    name = "donation"
    scope = "lowered"
    needs_compiled = True
    description = ("every engine-donated buffer is input->output aliased "
                   "in the compiled executable (no dropped donations, no "
                   "hidden EF-page copies, no donation-unused warnings)")

    def run(self, low) -> List[Finding]:
        from repro.roofline.hlo import entry_io_aliases, entry_param_shapes
        out = []
        text = low.compiled_text
        aliases = entry_io_aliases(text)
        params = entry_param_shapes(text)
        expect = _expected_aliased_shapes(low)
        n_expected = sum(expect.values())
        if len(aliases) != n_expected:
            out.append(self.finding(
                low.point, f"compiled executable aliases {len(aliases)} "
                f"buffer(s), but the engine donates {n_expected} leaves "
                f"({low.donate_argnums}) — donation dropped or a hidden "
                f"copy inserted"))
        aliased_params = {p for _, p in aliases}
        if len(aliased_params) != len(aliases):
            out.append(self.finding(
                low.point, "a parameter is aliased to two outputs in "
                "input_output_alias — malformed donation"))
        got = Counter()
        for _, p in aliases:
            if p < len(params):
                dt, dims = params[p]
                got[f"{dt}[{dims}]"] += 1
        if params and got != expect:
            missing = expect - got
            extra = got - expect
            out.append(self.finding(
                low.point, f"aliased buffer shapes differ from the donated "
                f"leaves: missing {dict(missing)} unexpected {dict(extra)}"))
        for w in low.compile_warnings:
            if "donat" in w.lower():
                out.append(self.finding(
                    low.point, f"donation warning at compile time: {w}"))
        return out


@register_pass
class CollectiveBytesPass(AnalysisPass):
    name = "collective-bytes"
    scope = "lowered"
    needs_compiled = True
    description = ("lowered HLO all-reduce count/bytes == the jaxpr "
                   "execution model; codec wire model consistent "
                   "(compressed < ideal, ladder monotone)")

    def run(self, low) -> List[Finding]:
        from repro.roofline.hlo import collective_summary
        out = []
        spec = low.spec
        # wire-model audit runs everywhere (it needs no device program)
        ideal = low.ideal_model_bytes
        if low.uplink is not None:
            if low.wire_up > ideal:
                out.append(self.finding(
                    low.point, f"uplink codec charges {low.wire_up} wire "
                    f"bytes, above the ideal f32 model ({ideal}) — the "
                    f"compression accounting is inverted"))
            if low.wire_down is not None and low.wire_down > ideal:
                out.append(self.finding(
                    low.point, f"downlink codec charges {low.wire_down} > "
                    f"ideal {ideal} wire bytes"))
        if low.level_bytes is not None:
            lv = low.level_bytes
            if list(lv) != sorted(lv):
                out.append(self.finding(
                    low.point, f"ladder level_bytes {lv} not ascending"))
            if lv and low.wire_up is not None and lv[-1] != low.wire_up:
                out.append(self.finding(
                    low.point, f"ladder top rung charges {lv[-1]} bytes, "
                    f"static wire model charges {low.wire_up} — the "
                    f"capacity rung must BE the configured codec"))
        if not spec.sharded:
            return out
        ops, nbytes = collective_execution_model(low.jaxpr)
        hlo = collective_summary(low.compiled_text)
        other = {k: v for k, v in hlo.items() if k != "all-reduce"}
        if other:
            out.append(self.finding(
                low.point, f"compiled module contains non-all-reduce "
                f"collectives {other}; psum lowers to all-reduce only"))
        hlo_ops, hlo_bytes = hlo.get("all-reduce", (0, 0))
        if (hlo_ops, hlo_bytes) != (ops, nbytes):
            out.append(self.finding(
                low.point, f"HLO all-reduce model ({hlo_ops} ops, "
                f"{hlo_bytes} B) != jaxpr execution model ({ops} ops, "
                f"{nbytes} B) — XLA inserted or dropped collective "
                f"traffic the bytes model does not account for"))
        return out


# a part's name as one segment of a name stack, whatever transforms wrap
# it: "codec/...", ".../vmap(local_train)/...", "transpose(jvp(eval))"
_PART_RE = re.compile(r"(?:^|[/(])(" + "|".join(PARTS) + r")(?=$|[/)])")

# the one equation JAX itself adds under no part: vmap moving an
# unbatched output (a telemetry tap's round-wide level) onto the client
# axis as it leaves the vmapped client step
_VMAP_EXIT = re.compile(r"^vmap\(\)(/vmap\(\))*$")


def _plumbing(stack, eqn) -> bool:
    return (eqn.primitive.name == "broadcast_in_dim"
            and _VMAP_EXIT.match(stack) is not None)


# primitives that must sit under given parts
_ANCHORS = {"sort": ("codec",), "top_k": ("codec",),
            "conv_general_dilated": ("local_train", "eval")}


def parts_of(name_stack: str) -> frozenset:
    """The round parts a name stack (or a device op's ``tf_op``) lies
    under."""
    return frozenset(_PART_RE.findall(name_stack))


def scope_findings(body) -> List[str]:
    """What breaks the scope contract in a round body (or any traced
    round-part jaxpr): equations under no part, under two, or in the
    wrong part."""
    bare, multi, misplaced = Counter(), Counter(), Counter()
    for stack, eqn in leaf_eqns_with_name_stacks(body):
        prim = eqn.primitive.name
        found = parts_of(stack)
        if not found and not _plumbing(stack, eqn):
            bare[prim] += 1
        elif len(found) > 1:
            multi[stack] += 1
        want = _ANCHORS.get(prim)
        if want and not found & set(want):
            misplaced[f"{prim} under {stack!r}"] += 1
    out = []
    if bare:
        out.append(f"round-body equations under none of {PARTS}: "
                   f"{dict(bare)}")
    if multi:
        out.append(f"equations under two parts at once: {dict(multi)}")
    if misplaced:
        out.append(f"equations in the wrong part: {dict(misplaced)}")
    return out


@register_pass
class ScopesPass(AnalysisPass):
    name = "scopes"
    scope = "lowered"
    description = ("every round-body equation lies under exactly one of "
                   "local_train / codec / aggregate / eval (vmap's "
                   "broadcast of an unbatched output excepted); sorts "
                   "under codec, convs under local_train or eval")

    def run(self, low) -> List[Finding]:
        body = round_body(low.jaxpr)
        if body is None:
            return [self.finding(low.point,
                                 "no round scan found in the superstep")]
        return [self.finding(low.point, m) for m in scope_findings(body)]
