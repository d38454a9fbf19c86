"""RNG-lineage checking: the PR 8 correlated-streams bug class, machine-checked.

Two layers:

* `rng_report` — a dataflow checker over a traced jaxpr.  PRNG keys are
  value-numbered structurally (`random_wrap` / `random_fold_in` /
  `random_split` build canonical tokens, so two `fold_in(key, pos)` calls
  with the same parent and the same position operand produce the SAME
  canonical key — exactly how the PR 8 bug looked in the trace).  A canonical
  key consumed by two independent sampling sites without an intervening
  split/fold is flagged (`reused-key`), as is a loop-invariant key consumed
  inside a scan/while body (`loop-reuse`: every iteration would redraw the
  same numbers).

* `sweep_fold_in_sites` — a source-level (AST) sweep that inventories every
  `fold_in` call under `src/repro` and requires each to carry a registered
  stream tag (`repro.analysis.streams`): an inline tag constant, or a
  ``# rng-stream: <name>`` marker for counter-folds whose independence comes
  from an upstream tagging fold.  New unregistered `fold_in` sites fail
  `python -m repro.analysis check`.

The subsampling literature (arXiv:2105.01552, arXiv:2205.08588) is explicit
that draw independence and inclusion-probability bookkeeping are
correctness-critical for the estimators this repo ships — stream hygiene is
not a style rule here.
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
import re

import jax
import numpy as np

from repro.analysis import streams as streams_mod

from jax.extend.core import Literal as _JaxLiteral
_LITERAL_TYPES = (_JaxLiteral,)

# --------------------------------------------------------------------------- #
# jaxpr lineage checker
# --------------------------------------------------------------------------- #

# primitives that DERIVE fresh keys / move key values without consuming them
_DERIVE = frozenset({
    "random_wrap", "random_unwrap", "random_fold_in", "random_split",
    "random_clone", "copy",
})
_KEY_VIEW = frozenset({
    "slice", "dynamic_slice", "squeeze", "reshape", "broadcast_in_dim",
    "gather", "transpose", "concatenate",
})

#: sentinel site: inside a sampling-wrapper boundary (consumption already
#: recorded at the wrapper eqn; inner extractions are the same logical draw)
_SUPPRESS = object()


def _is_key_aval(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    try:
        return jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key)
    except TypeError:
        return False


@dataclasses.dataclass(frozen=True)
class _Canon:
    """Canonical value token + loop-variance taint."""

    token: tuple
    varies: bool = False


def _lit_canon(val) -> _Canon:
    if np.ndim(val) == 0:
        try:
            return _Canon(("lit", val.item() if hasattr(val, "item") else val))
        except (TypeError, ValueError):
            pass
    return _Canon(("lit-arr", id(val)))


@dataclasses.dataclass
class RngIssue:
    """One lineage violation found in a traced program."""

    kind: str            # "reused-key" | "loop-reuse"
    key: str             # canonical token (human-readable repr)
    sites: list[str]     # consuming call sites (jax-internal wrapper names)
    detail: str

    def __str__(self):
        return f"[{self.kind}] {self.detail}"


@dataclasses.dataclass
class RngReport:
    """All consumptions seen plus the violations derived from them."""

    issues: list = dataclasses.field(default_factory=list)
    consumptions: int = 0
    keys_seen: int = 0

    @property
    def ok(self) -> bool:
        """True when no lineage violation was found."""
        return not self.issues


class _Lineage:
    def __init__(self):
        # canonical key -> {site_id: site_name}; site = outermost jax-internal
        # sampling wrapper (pjit whose name starts with "_") or the bits eqn
        self.consumers: dict[tuple, dict[int, str]] = {}
        self.loop_hits: dict[tuple, str] = {}
        self.n_consumptions = 0
        self.key_tokens: set = set()

    def consume(self, canon: _Canon, site_id: int, site_name: str, mult: float):
        """Record `canon` being drawn from at one sampling site."""
        self.n_consumptions += 1
        self.consumers.setdefault(canon.token, {})[site_id] = site_name
        if mult > 1.0 and not canon.varies:
            self.loop_hits.setdefault(canon.token, site_name)

    def _walk(self, jaxpr, env: dict, mult: float, site):
        jaxpr = _as_open(jaxpr)
        for eqn in jaxpr.eqns:

            def canon_of(v):
                if isinstance(v, _LITERAL_TYPES):
                    return _lit_canon(v.val)
                if v in env:
                    return env[v]
                c = _Canon(("free", id(v)))
                env[v] = c
                return c

            name = eqn.primitive.name
            ins = [canon_of(v) for v in eqn.invars]
            varies = any(c.varies for c in ins)

            if name in ("random_wrap", "random_unwrap", "random_fold_in",
                        "random_split", "random_bits") and site is _SUPPRESS:
                # inside a sampling-wrapper boundary: derivations/extractions
                # are implementation detail of ONE logical draw (randint
                # splits its key; choice shuffles) — already recorded at the
                # boundary, so only propagate canon tokens here
                tok = ("inner", ins[0].token if ins else (), name, id(eqn))
                for ov in eqn.outvars:
                    env[ov] = _Canon(tok, varies)
                continue
            if name == "random_wrap":
                tok = ins[0].token
                if tok[0] == "unwrap":
                    out = _Canon(tok[1], varies)
                else:
                    out = _Canon(("wrap", tok), varies)
                env[eqn.outvars[0]] = out
            elif name == "random_unwrap":
                tok = ins[0].token
                if tok[0] == "wrap":
                    out = _Canon(tok[1], varies)
                else:
                    out = _Canon(("unwrap", tok), varies)
                env[eqn.outvars[0]] = out
            elif name == "random_fold_in":
                out = _Canon(("fold", ins[0].token, ins[1].token), varies)
                env[eqn.outvars[0]] = out
                self.key_tokens.add(out.token)
            elif name == "random_split":
                out = _Canon(("split", ins[0].token,
                              str(eqn.params.get("shape"))), varies)
                env[eqn.outvars[0]] = out
            elif name == "random_bits":
                self.consume(ins[0], id(eqn), "random_bits", mult)
                self.key_tokens.add(ins[0].token)
                for ov in eqn.outvars:
                    env[ov] = _Canon(("bits", ins[0].token), varies)
            else:
                subs = _call_subs(eqn)
                if subs:
                    for sub, factor, invar_map, out_map, sub_site in subs:
                        nxt_site = site
                        if sub_site is not None and site is not _SUPPRESS:
                            # a jax-internal sampling wrapper (_uniform,
                            # _randint, _choice, ...) consumes its key
                            # operands HERE — everything inside is one draw
                            for i, v in enumerate(eqn.invars):
                                if _is_key_aval(getattr(v, "aval", None)):
                                    self.consume(ins[i], id(eqn), sub_site,
                                                 mult)
                                    self.key_tokens.add(ins[i].token)
                            nxt_site = _SUPPRESS
                        sub_env = {}
                        for sub_v, outer_idx, force_vary in invar_map:
                            base = (ins[outer_idx] if outer_idx < len(ins)
                                    else _Canon(("pad", outer_idx)))
                            if force_vary:
                                base = _Canon(("loopvar", base.token),
                                              True)
                            sub_env[sub_v] = base
                        self._walk(sub, sub_env, mult * factor, nxt_site)
                        for sub_out, outer_out in out_map:
                            env[outer_out] = sub_env.get(
                                sub_out, _Canon(("out", id(outer_out))))
                    continue
                # structural value-numbering for plain ops (so fold data like
                # `pos + 1` canonicalizes); key-typed operands hitting a
                # non-derive primitive count as consumption
                for i, v in enumerate(eqn.invars):
                    aval = getattr(v, "aval", None)
                    if (_is_key_aval(aval) and name not in _DERIVE
                            and name not in _KEY_VIEW
                            and site is not _SUPPRESS):
                        self.consume(ins[i], id(eqn), name, mult)
                tok = ("prim", name,
                       tuple(c.token for c in ins), _params_key(eqn.params))
                for j, ov in enumerate(eqn.outvars):
                    env[ov] = _Canon(tok + (j,), varies)

    def issues(self) -> list[RngIssue]:
        """Materialize reused-key / loop-reuse findings from the lineage."""
        out = []
        for tok, sites in self.consumers.items():
            if len(sites) >= 2:
                out.append(RngIssue(
                    kind="reused-key",
                    key=repr(tok),
                    sites=sorted(set(sites.values())),
                    detail=(
                        f"key {tok!r} consumed by {len(sites)} independent "
                        f"sampling sites ({sorted(set(sites.values()))}) "
                        "without an intervening split/fold_in"
                    ),
                ))
        for tok, site in self.loop_hits.items():
            out.append(RngIssue(
                kind="loop-reuse",
                key=repr(tok),
                sites=[site],
                detail=(
                    f"loop-invariant key {tok!r} consumed inside a "
                    f"scan/while body at site {site!r} — every iteration "
                    "redraws the same numbers (fold in the loop counter)"
                ),
            ))
        return out


def _as_open(j):
    return j.jaxpr if hasattr(j, "jaxpr") and hasattr(j, "consts") else j


def _params_key(params) -> str:
    try:
        return str(sorted((k, str(v)) for k, v in params.items()
                          if not hasattr(v, "eqns") and not hasattr(v, "jaxpr")))
    except Exception:
        return "?"


def _call_subs(eqn):
    """For call-like eqns: (sub_jaxpr, mult_factor, invar_map, out_map, site).

    invar_map: (sub_invar, outer_invar_index, force_vary) triples.
    out_map: (sub_outvar, outer_outvar) pairs.  site: a jax-internal sampling
    wrapper name ("_uniform", "_normal", ...) or None.
    """
    name = eqn.primitive.name
    if name == "scan":
        closed = eqn.params["jaxpr"]
        sub = _as_open(closed)
        n_consts = eqn.params.get("num_consts", 0)
        n_carry = eqn.params.get("num_carry", 0)
        length = float(eqn.params.get("length", 1) or 1)
        invar_map = []
        for i, sv in enumerate(sub.invars):
            vary = i >= n_consts          # carry + xs vary per iteration
            invar_map.append((sv, i, vary))
        del n_carry  # outvars align positionally: [carry..., ys...]
        out_map = list(zip(sub.outvars, eqn.outvars))
        return [(sub, length, invar_map, out_map, None)]
    if name == "while":
        body = _as_open(eqn.params["body_jaxpr"])
        cond = _as_open(eqn.params["cond_jaxpr"])
        nb = eqn.params.get("body_nconsts", 0)
        nc = eqn.params.get("cond_nconsts", 0)
        from repro.analysis.trace import _while_trip_count

        trips = _while_trip_count(eqn)
        body_map = [(sv, nc + i, i >= nb) for i, sv in enumerate(body.invars)]
        cond_map = [
            (sv, (i if i < nc else nc + nb + (i - nc)), i >= nc)
            for i, sv in enumerate(cond.invars)
        ]
        return [(cond, trips, cond_map, [], None),
                (body, trips, body_map, list(zip(body.outvars, eqn.outvars)),
                 None)]
    if name == "cond":
        out = []
        branches = eqn.params.get("branches", ())
        for br in branches:
            sub = _as_open(br)
            invar_map = [(sv, i + 1, False) for i, sv in enumerate(sub.invars)]
            out.append((sub, 1.0, invar_map,
                        list(zip(sub.outvars, eqn.outvars)), None))
        return out
    closed = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    if closed is not None and (hasattr(closed, "eqns")
                               or hasattr(closed, "jaxpr")):
        sub = _as_open(closed)
        pjit_name = eqn.params.get("name")
        site = pjit_name if (isinstance(pjit_name, str)
                             and pjit_name.startswith("_")) else None
        invar_map = [(sv, i, False) for i, sv in enumerate(sub.invars)]
        return [(sub, 1.0, invar_map,
                 list(zip(sub.outvars, eqn.outvars)), site)]
    # other sub-jaxpr carriers (pallas_call, custom_jvp, ...): skip lineage
    # inside — they do not consume PRNG keys in this codebase
    return []


def report_from_jaxpr(jaxpr) -> RngReport:
    """Run the lineage checker over an already-traced Jaxpr/ClosedJaxpr."""
    lin = _Lineage()
    open_j = _as_open(jaxpr)
    env = {v: _Canon(("in", i)) for i, v in enumerate(open_j.invars)}
    for i, v in enumerate(getattr(open_j, "constvars", ())):
        env[v] = _Canon(("const", i))
    lin._walk(open_j, env, 1.0, None)
    return RngReport(issues=lin.issues(),
                     consumptions=lin.n_consumptions,
                     keys_seen=len(lin.key_tokens))


def rng_report(fn, *args, **kwargs) -> RngReport:
    """Trace `fn(*args, **kwargs)` and run the lineage checker."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return report_from_jaxpr(closed)


# --------------------------------------------------------------------------- #
# source-level fold_in sweep
# --------------------------------------------------------------------------- #

_MARKER = re.compile(r"#\s*rng-stream:\s*([\w\-]+)")

SRC_ROOT = pathlib.Path(__file__).resolve().parents[1]   # src/repro


@dataclasses.dataclass
class FoldInSite:
    """One `fold_in` call site found by the AST sweep."""

    path: str            # relative to src/repro
    lineno: int
    source: str          # the call's first source line, stripped
    stream: str | None   # registered stream satisfied here (None = violation)
    via: str             # "tag" | "marker" | "nested" | "unregistered"

    @property
    def ok(self) -> bool:
        """True when the site carries a registered stream tag or marker."""
        return self.stream is not None


def _tag_stream_name(node: ast.expr) -> str | None:
    """Stream name if `node` is a registered inline tag expression."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        s = streams_mod.stream_for_tag(node.value)
        return s.name if s else None
    ident = None
    if isinstance(node, ast.Name):
        ident = node.id
    elif isinstance(node, ast.Attribute):
        ident = node.attr
    if ident is not None:
        name = streams_mod.TAG_CONSTANT_TO_STREAM.get(ident)
        if name is not None:
            return name
    if isinstance(node, ast.BinOp):
        return _tag_stream_name(node.left) or _tag_stream_name(node.right)
    return None


def _is_fold_in(node: ast.Call) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr == "fold_in") or (
        isinstance(f, ast.Name) and f.id == "fold_in"
    )


def sweep_fold_in_sites(root: pathlib.Path | str = SRC_ROOT) -> list[FoldInSite]:
    """Inventory every `fold_in` call site under `root` (default src/repro).

    A site is compliant when its data argument is a registered tag constant
    (inline or `TAG + offset`), when its key argument is itself a compliant
    `fold_in` (the two-level tagged pattern), or when a ``# rng-stream:``
    marker naming a registered stream sits on the call line / the line above.
    """
    root = pathlib.Path(root)
    sites: list[FoldInSite] = []
    for path in sorted(root.rglob("*.py")):
        rel = str(path.relative_to(root))
        text = path.read_text()
        lines = text.splitlines()
        try:
            tree = ast.parse(text)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not _is_fold_in(node):
                continue
            args = list(node.args)
            stream = via = None
            if len(args) >= 2:
                stream = _tag_stream_name(args[1])
                via = "tag" if stream else None
                nested = (stream is None and isinstance(args[0], ast.Call)
                          and _is_fold_in(args[0]) and len(args[0].args) >= 2)
                if nested:
                    inner = _tag_stream_name(args[0].args[1])
                    if inner:
                        stream, via = inner, "nested"
            if stream is None:
                lo = max(node.lineno - 2, 0)
                hi = min(getattr(node, "end_lineno", node.lineno), len(lines))
                for ln in lines[lo:hi]:
                    m = _MARKER.search(ln)
                    if m and m.group(1) in streams_mod.REGISTRY:
                        stream, via = m.group(1), "marker"
                        break
            sites.append(FoldInSite(
                path=rel,
                lineno=node.lineno,
                source=lines[node.lineno - 1].strip(),
                stream=stream,
                via=via or "unregistered",
            ))
    return sites


def check_fold_in_sites(root: pathlib.Path | str = SRC_ROOT) -> list[FoldInSite]:
    """The violations: unregistered `fold_in` sites under `root`."""
    return [s for s in sweep_fold_in_sites(root) if not s.ok]
