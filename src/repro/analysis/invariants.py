"""Dataflow invariant checker: abstract interpretation over serving jaxprs.

The scan/merge/cascade pipeline leans on *value* contracts that no type
system sees: the shortlist ids entering the Pallas rescore kernel are
sorted and duplicate-free, and ``-1`` dedup/pad sentinels are masked to
``-inf`` before any top-k. Comments state those contracts; this pass
proves them, per traced entry point, by tracking value facts through
jaxpr equations:

  * ``asc``       — the non-sentinel subsequence is sorted ascending
  * ``distinct``  — the non-sentinel values are pairwise distinct
  * ``sentinels`` — negative values are dedup/pad sentinels by contract

with transfer rules for exactly the patterns the live code lowers to:
``sort`` introduces ``asc``; the ``_shortlist`` adjacent-duplicate mask
(``eq(x[1:], x[:-1])`` concatenated behind a leading ``False``) recognised
as a keep-first dup mask; ``where(dup, -1, x)`` on a sorted ``x`` yields
``{asc, sentinels, distinct}`` (the *swapped*-branch variant keeps only
duplicates and loses ``distinct``); ``where(ids >= 0, s, -inf)`` marks
scores as masked by those ids; reshape/broadcast/convert/pad(-1) preserve
facts when they preserve last-axis order. Facts that reach a **sink** are
checked:

  * **Pallas rescore** (``pallas_call`` with an int32 ``(1, U)`` ids
    operand): ids must be ``asc`` (``inv.rowids-order`` — ROADMAP
    follow-up (a), the block-skip guard contract) and ``distinct``
    (``inv.dedup-tiebreak`` — lowest-id-wins dedup), and the kernel body
    must mask ids-derived negative lanes to ``-inf``
    (``inv.sentinel-mask``), found structurally: a ``select_n`` whose
    predicate derives from the ids ref and whose branch is ``-inf``.
  * **jnp rescore** (``take_along_axis`` reporting sentinel-bearing ids
    selected by a ``top_k``): the top-k's score input must carry the
    ``masked-by-those-ids`` fact (``inv.sentinel-mask``), and the ids must
    be ``distinct`` (``inv.dedup-tiebreak``).

Unknown primitives drop facts, so the pass errs toward "cannot prove"
(a finding) rather than wrongly proving; each contract has a known-bad
fixture in ``analysis/fixtures/bad_invariants.py`` tripping exactly its
finding.
"""
from __future__ import annotations

import dataclasses
import math

import jax
from jax.extend.core import Literal

from repro.analysis import Finding
from repro.analysis.jaxpr_lints import _eqn_subjaxprs

_NEG_INF = float("-inf")


@dataclasses.dataclass(frozen=True)
class Fact:
    """Abstract value attached to one jaxpr var."""

    flags: frozenset = frozenset()     # subset of {asc, distinct, sentinels}
    const: object = None               # known uniform value (scalar fills)
    kind: str | None = None            # dupmask_dups | dupmask_keepfirst |
    #                                    ge0 | masked | slice
    origin: object = None              # provenance Var (mask/slice subject)
    start: int | None = None           # slice start along the last axis


_EMPTY = Fact()


def _scalar(x) -> object:
    try:
        return x.item() if hasattr(x, "item") and getattr(x, "size", 2) == 1 \
            else x if isinstance(x, (int, float, bool)) else None
    except (TypeError, ValueError):
        return None


class _Interp:
    """One entry point's interpretation; findings accumulate on self."""

    def __init__(self, label: str):
        self.label = label
        self.findings: list[Finding] = []
        self._seen_sinks: set[int] = set()

    # -- facts ------------------------------------------------------------

    def fact(self, env, v) -> Fact:
        if isinstance(v, Literal):
            return Fact(const=_scalar(v.val))
        return env.get(v, _EMPTY)

    @staticmethod
    def _ident(env, v):
        """The provenance identity of ``v``: its fact origin, else itself."""
        if isinstance(v, Literal):
            return None
        f = env.get(v)
        return f.origin if f is not None and f.origin is not None else v

    # -- interpretation ---------------------------------------------------

    def run_jaxpr(self, jaxpr, env) -> None:
        producer = {}
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                producer[v] = eqn
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env, producer)

    def _recurse(self, eqn, env) -> None:
        """Generic call boundary: map facts in, interpret, map facts out."""
        closed = eqn.params.get("jaxpr")
        if closed is None:
            return
        inner = closed.jaxpr
        ienv: dict = {}
        for cv, cval in zip(inner.constvars, closed.consts):
            ienv[cv] = Fact(const=_scalar(cval))
        for iv, ov in zip(inner.invars, eqn.invars):
            f = self.fact(env, ov)
            if f is not _EMPTY or f.const is not None:
                # keep OUTER provenance identity across the boundary so
                # mask origins still match after re-entering the caller
                ienv[iv] = f if f.origin is not None or isinstance(
                    ov, Literal) else dataclasses.replace(
                        f, origin=ov)
        self.run_jaxpr(inner, ienv)
        for ov, iv in zip(eqn.outvars, inner.outvars):
            f = ienv.get(iv)
            if f is not None:
                env[ov] = f

    def _eqn(self, eqn, env, producer) -> None:
        name = eqn.primitive.name
        if name == "jit":
            if eqn.params.get("name") == "take_along_axis":
                self._jnp_rescore_sink(eqn, env, producer)
            self._recurse(eqn, env)
            return
        if name == "pallas_call":
            self._pallas_rescore_sink(eqn, env)
            return
        if name in ("scan", "while", "cond", "shard_map", "custom_jvp_call",
                    "custom_vjp_call", "remat"):
            return                               # facts do not flow through
        handler = getattr(self, f"_p_{name}", None)
        if handler is not None:
            handler(eqn, env)
        # every unhandled primitive drops facts (conservative)

    # -- transfer rules ---------------------------------------------------

    def _p_sort(self, eqn, env):
        dim = eqn.params.get("dimension", -1)
        aval = eqn.invars[0].aval
        if aval.shape and dim in (-1, len(aval.shape) - 1):
            env[eqn.outvars[0]] = Fact(flags=frozenset({"asc"}))

    def _p_slice(self, eqn, env):
        src = eqn.invars[0]
        aval = src.aval
        if not aval.shape:
            return
        starts = tuple(eqn.params["start_indices"])
        limits = tuple(eqn.params["limit_indices"])
        # last-axis-only slice: every leading dim taken whole
        for i, (s, li) in enumerate(zip(starts[:-1], limits[:-1])):
            if s != 0 or li != aval.shape[i]:
                return
        f = self.fact(env, src)
        env[eqn.outvars[0]] = Fact(
            flags=f.flags, kind="slice", origin=self._ident(env, src),
            start=int(starts[-1]))

    def _p_eq(self, eqn, env):
        a, b = (self.fact(env, v) for v in eqn.invars[:2])
        if (a.kind == "slice" and b.kind == "slice"
                and a.origin is b.origin and a.origin is not None
                and {a.start, b.start} == {0, 1}):
            env[eqn.outvars[0]] = Fact(kind="dupmask_dups", origin=a.origin)

    def _p_concatenate(self, eqn, env):
        if len(eqn.invars) != 2:
            return
        head, tail = (self.fact(env, v) for v in eqn.invars)
        head_len = eqn.invars[0].aval.shape[-1] \
            if eqn.invars[0].aval.shape else 0
        if (head.const is False and head_len == 1
                and tail.kind == "dupmask_dups"):
            env[eqn.outvars[0]] = Fact(kind="dupmask_keepfirst",
                                       origin=tail.origin)

    def _p_broadcast_in_dim(self, eqn, env):
        src = eqn.invars[0]
        f = self.fact(env, src)
        in_shape = src.aval.shape if hasattr(src, "aval") else ()
        if not in_shape or math.prod(in_shape) == 1:
            if f.const is not None:
                env[eqn.outvars[0]] = Fact(const=f.const)
            return
        bd = tuple(eqn.params["broadcast_dimensions"])
        out_ndim = len(eqn.outvars[0].aval.shape)
        if bd and bd[-1] == out_ndim - 1:        # last axis preserved
            env[eqn.outvars[0]] = dataclasses.replace(
                f, origin=self._ident(env, src))

    def _p_reshape(self, eqn, env):
        src = eqn.invars[0]
        f = self.fact(env, src)
        if f is _EMPTY:
            return
        a = tuple(d for d in src.aval.shape if d != 1)
        b = tuple(d for d in eqn.outvars[0].aval.shape if d != 1)
        if a == b:                               # only unit dims moved
            env[eqn.outvars[0]] = dataclasses.replace(
                f, origin=self._ident(env, src))

    def _p_convert_element_type(self, eqn, env):
        f = self.fact(env, eqn.invars[0])
        if f is not _EMPTY:
            env[eqn.outvars[0]] = dataclasses.replace(
                f, origin=self._ident(env, eqn.invars[0]))

    def _p_squeeze(self, eqn, env):
        self._p_convert_element_type(eqn, env)

    def _p_pad(self, eqn, env):
        f = self.fact(env, eqn.invars[0])
        padval = self.fact(env, eqn.invars[1]).const
        cfg = eqn.params["padding_config"]
        if (f.flags and padval is not None and padval < 0
                and all(int(interior) == 0 and int(lo) >= 0
                        for lo, _hi, interior in cfg)):
            env[eqn.outvars[0]] = dataclasses.replace(
                f, flags=f.flags | {"sentinels"})

    def _p_ge(self, eqn, env):
        rhs = self.fact(env, eqn.invars[1]).const
        subject = self._ident(env, eqn.invars[0])
        if rhs == 0 and subject is not None:
            env[eqn.outvars[0]] = Fact(kind="ge0", origin=subject)

    def _p_and(self, eqn, env):
        # narrowing a >=0 mask only masks MORE lanes to -inf — the
        # sentinel-masking contract direction survives conjunction
        for v in eqn.invars:
            f = self.fact(env, v)
            if f.kind == "ge0":
                env[eqn.outvars[0]] = f
                return

    def _p_select_n(self, eqn, env):
        pred, case0, case1 = eqn.invars[:3]
        pf = self.fact(env, pred)
        f0, f1 = self.fact(env, case0), self.fact(env, case1)
        out = eqn.outvars[0]
        if pf.kind == "dupmask_keepfirst":
            neg1 = f1.const is not None and f1.const < 0
            neg0 = f0.const is not None and f0.const < 0
            keeps0 = ("asc" in f0.flags
                      and self._ident(env, case0) is pf.origin)
            keeps1 = ("asc" in f1.flags
                      and self._ident(env, case1) is pf.origin)
            if keeps0 and neg1:
                # where(dup, -1, sorted): first occurrence survives ⇒
                # non-sentinels are strictly increasing
                env[out] = Fact(flags=frozenset({"asc", "sentinels",
                                                 "distinct"}))
            elif keeps1 and neg0:
                # swapped branches: only the DUPLICATES survive — still
                # sorted, but repeated values break the lowest-id dedup
                env[out] = Fact(flags=frozenset({"asc", "sentinels"}))
            return
        if pf.kind == "ge0":
            if f0.const == _NEG_INF:
                env[out] = Fact(kind="masked", origin=pf.origin)
            elif f1.const == _NEG_INF:
                # inverted where(ids >= 0, -inf, s): masks the LIVE lanes
                return
            return

    # -- sinks ------------------------------------------------------------

    def _pallas_rescore_sink(self, eqn, env) -> None:
        ids_pos = None
        for pos, v in enumerate(eqn.invars):
            aval = getattr(v, "aval", None)
            if (aval is not None and str(aval.dtype) == "int32"
                    and len(aval.shape) == 2 and aval.shape[0] == 1
                    and aval.shape[1] > 1):
                ids_pos = pos
                break
        if ids_pos is None:
            return                                # plain mode: no contract
        if id(eqn) in self._seen_sinks:
            return
        self._seen_sinks.add(id(eqn))
        f = self.fact(env, eqn.invars[ids_pos])
        where = f"{self.label}:pallas-rescore"
        if "asc" not in f.flags:
            self.findings.append(Finding(
                check="inv.rowids-order", where=where,
                message=(f"{self.label}: cannot prove the row_ids operand "
                         f"of the rescore pallas_call is sorted ascending "
                         f"— the block-skip guard's strict-improvement "
                         f"skip and the shortlist contract assume a "
                         f"sorted, deduplicated id stream")))
            return
        if "distinct" not in f.flags:
            self.findings.append(Finding(
                check="inv.dedup-tiebreak", where=where,
                message=(f"{self.label}: row_ids reach the rescore kernel "
                         f"sorted but not provably duplicate-free — "
                         f"repeated ids break the lowest-id-wins dedup "
                         f"(_shortlist keep-first contract)")))
            return
        if not self._kernel_masks_ids(eqn, ids_pos):
            self.findings.append(Finding(
                check="inv.sentinel-mask", where=where,
                message=(f"{self.label}: the rescore kernel never masks "
                         f"ids-derived negative lanes to -inf — a -1 "
                         f"dedup/pad sentinel's score could surface as a "
                         f"real result")))

    @staticmethod
    def _kernel_masks_ids(eqn, ids_pos: int) -> bool:
        """Structurally: some ``select_n`` in the kernel body has a
        predicate derived from the ids ref and a ``-inf`` branch."""
        kernel = eqn.params["jaxpr"]
        kj = kernel.jaxpr if hasattr(kernel, "jaxpr") else kernel
        if ids_pos >= len(kj.invars):
            return False
        derived = {kj.invars[ids_pos]}
        neginf = set()

        def scan(jaxpr):
            hit = False
            for e in jaxpr.eqns:
                lit_neg = any(isinstance(v, Literal)
                              and _scalar(v.val) == _NEG_INF
                              for v in e.invars)
                if lit_neg or any(v in neginf for v in e.invars
                                  if not isinstance(v, Literal)):
                    if e.primitive.name in ("broadcast_in_dim",
                                            "convert_element_type"):
                        neginf.update(e.outvars)
                if e.primitive.name == "select_n":
                    pred = e.invars[0]
                    cases = e.invars[1:]
                    if (not isinstance(pred, Literal)
                            and pred in derived
                            and any((not isinstance(c, Literal)
                                     and c in neginf)
                                    or (isinstance(c, Literal)
                                        and _scalar(c.val) == _NEG_INF)
                                    for c in cases)):
                        hit = True
                if any(not isinstance(v, Literal) and v in derived
                       for v in e.invars):
                    derived.update(e.outvars)
                closed = e.params.get("jaxpr")
                if closed is not None and hasattr(closed, "jaxpr"):
                    # jnp.where traces as jit[_where] even inside kernel
                    # bodies — carry the derived/-inf sets across the call
                    # boundary, then back out to the call's outvars
                    sub = closed.jaxpr
                    for iv, ov in zip(sub.invars, e.invars):
                        if isinstance(ov, Literal):
                            if _scalar(ov.val) == _NEG_INF:
                                neginf.add(iv)
                            continue
                        if ov in derived:
                            derived.add(iv)
                        if ov in neginf:
                            neginf.add(iv)
                    inner_hit = scan(sub)
                    hit = inner_hit or hit
                    for ov, iv in zip(e.outvars, sub.outvars):
                        if not isinstance(iv, Literal):
                            if iv in derived:
                                derived.add(ov)
                            if iv in neginf:
                                neginf.add(ov)
                else:
                    for sub in _eqn_subjaxprs(e):
                        hit = scan(sub) or hit
            return hit

        return scan(kj)

    def _jnp_rescore_sink(self, eqn, env, producer) -> None:
        ids_f = self.fact(env, eqn.invars[0])
        if "sentinels" not in ids_f.flags:
            return                                # not a rescore select
        idx = eqn.invars[1]
        src = producer.get(idx)
        if src is None or src.primitive.name != "top_k":
            return
        if id(src) in self._seen_sinks:
            return
        self._seen_sinks.add(id(src))
        where = f"{self.label}:jnp-rescore"
        if "distinct" not in ids_f.flags:
            self.findings.append(Finding(
                check="inv.dedup-tiebreak", where=where,
                message=(f"{self.label}: the rescore top-k reports "
                         f"sentinel-bearing ids that are not provably "
                         f"duplicate-free — a document could surface "
                         f"twice in one result list")))
            return
        score_f = self.fact(env, src.invars[0])
        ids_origin = self._ident(env, eqn.invars[0])
        if score_f.kind != "masked" or score_f.origin is not ids_origin:
            self.findings.append(Finding(
                check="inv.sentinel-mask", where=where,
                message=(f"{self.label}: rescore scores reach top_k "
                         f"without the where(ids >= 0, s, -inf) sentinel "
                         f"mask — a -1 dedup/pad slot's score competes as "
                         f"a real document")))


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def check_entry(label: str, fn, args) -> list[Finding]:
    """All invariant checks for one traced entry point."""
    closed = jax.make_jaxpr(fn)(*args)
    interp = _Interp(label)
    env: dict = {}
    for cv, cval in zip(closed.jaxpr.constvars, closed.consts):
        env[cv] = Fact(const=_scalar(cval))
    interp.run_jaxpr(closed.jaxpr, env)
    return interp.findings


def run() -> list[Finding]:
    """Prove the pipeline contracts on every serving entry point."""
    from repro.analysis.jaxpr_lints import serving_entry_points
    findings: list[Finding] = []
    for ep in serving_entry_points():
        findings += check_entry(ep.label, ep.fn, ep.args)
    return findings
