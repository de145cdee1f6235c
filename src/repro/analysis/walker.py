"""Recursive jaxpr/HLO walker: the traversal layer every pass shares.

The old test-private walkers (``tests/test_fused_serving.py::_shapes_in``
and the migration HLO grep in ``tests/test_tiered_runtime.py``) each
re-implemented sub-jaxpr discovery with bespoke handling of nested
``ClosedJaxpr``/``Jaxpr`` leaves, and neither tracked dataflow.  This
module centralizes both:

  * ``collect_eqns``: one pre-order traversal yields EVERY equation of a
    program — through ``jit``, ``scan``, ``while``, ``cond``,
    ``closed_call``, ``custom_jvp/vjp_call``, ``remat`` and
    ``pallas_call`` — annotated with output/input avals, call-stack path,
    and a raw-KV taint bit;
  * taint: inputs the caller marks as KV sources stay "raw" through
    layout/selection-preserving ops (reshape, gather, scatter, slice,
    convert, select, …) and degrade to "derived" through arithmetic.  A
    dot with a *raw* operand is an attention-read dot (q·k or p·v) — the
    surface whose accumulation dtype the f32 pass checks; a dot whose
    operands are merely derived (e.g. attention output @ w_o) is ordinary
    network compute.  Equations inside ``pallas_call`` kernels carry
    ``in_pallas=True`` instead: ref-mediated dataflow defeats value
    tainting, and every kernel registered here is an attention kernel, so
    passes treat all pallas dots as read-path dots;
  * HLO: ``lower_hlo_text`` compiles a function and returns the optimized
    module text; ``hlo_ops_present`` reports which of a set of op names
    appear in it (the collective-absence pin).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

import jax
from jax.extend import core as jex_core

# Taint lattice: NONE < DERIVED < RAW.  RAW marks values that still *are*
# the KV bytes (possibly re-laid-out / masked); DERIVED marks values merely
# computed from them (scores, probabilities, attention outputs).
TAINT_NONE, TAINT_DERIVED, TAINT_RAW = 0, 1, 2

# Primitives through which RAW taint survives: they move, select or re-type
# the same values without arithmetic that would launder them into "derived".
_TRANSPARENT_PRIMS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "dynamic_update_slice", "gather", "scatter",
    "scatter-add", "concatenate", "convert_element_type", "select_n",
    "rev", "pad", "copy", "stop_gradient", "squeeze", "split",
})

# Param keys under which call-like primitives store their sub-jaxpr when the
# eqn invars map 1:1 onto the sub-jaxpr invars (taint can flow exactly).
_ONE_TO_ONE_CALL_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


@dataclass
class WalkedEqn:
    """One equation seen by the recursive traversal."""
    prim: str                      # primitive name
    out_avals: list                # output ShapedArray-likes
    in_avals: list                 # input avals (literals included)
    in_taints: list[int]           # taint level per input
    params: dict                   # raw eqn params
    path: tuple[str, ...]          # call-stack of enclosing primitives
    in_pallas: bool = False        # inside a pallas_call kernel jaxpr
    source: str = ""               # best-effort "file:line" provenance
    cast_f32: bool = False         # dot only: result is immediately
                                   # convert_element_type'd to f32/f64 in
                                   # the same jaxpr (the "explicit cast"
                                   # accumulation idiom)


def _aval(v):
    return getattr(v, "aval", None)


def _source_of(eqn) -> str:
    info = getattr(eqn, "source_info", None)
    try:
        frame = jax.api_util.user_frame(info.traceback) \
            if info is not None and info.traceback is not None else None
    except Exception:
        frame = None
    if frame is None:
        return ""
    return f"{frame.file_name}:{frame.start_line}"


def _sub_jaxprs_generic(params: dict):
    """Every (key, ClosedJaxpr|Jaxpr) nested anywhere in eqn params — the
    uniform discovery the old per-test walkers botched case by case."""
    for key, val in params.items():
        for sub in jax.tree_util.tree_leaves(
                val, is_leaf=lambda x: isinstance(
                    x, (jex_core.Jaxpr, jex_core.ClosedJaxpr))):
            if isinstance(sub, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
                yield key, sub


def _as_jaxpr(sub) -> jex_core.Jaxpr:
    return sub.jaxpr if isinstance(sub, jex_core.ClosedJaxpr) else sub


def _call_taint_map(eqn, sub: jex_core.Jaxpr,
                    taint_of: Callable[[Any], int]) -> dict:
    """Map caller-side taint onto a sub-jaxpr's invars.

    Exact mappings for the structured control-flow primitives; for anything
    else, a positional map when lengths line up, else RAW/DERIVED collapse
    onto every sub invar (conservative: never silently drops taint)."""
    name = eqn.primitive.name
    in_t = [taint_of(v) for v in eqn.invars]
    sub_in = list(sub.invars)
    if name == "while":
        # invars = cond_consts + body_consts + carry;
        # body invars = body_consts + carry; cond invars = cond_consts+carry
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        body = _as_jaxpr(eqn.params["body_jaxpr"])
        if sub is body:
            src = in_t[cn:]
        else:
            src = in_t[:cn] + in_t[cn + bn:]
        if len(src) == len(sub_in):
            return dict(zip(sub_in, src))
    elif name == "cond":
        src = in_t[1:]                       # invars[0] is the branch index
        if len(src) == len(sub_in):
            return dict(zip(sub_in, src))
    elif len(in_t) == len(sub_in):
        # jit / scan / closed_call / custom_* : 1:1 by construction
        # (scan: consts + carry + xs in both frames)
        return dict(zip(sub_in, in_t))
    elif len(in_t) <= len(sub_in):
        # pallas_call: invars map onto the leading input refs; the trailing
        # output/scratch refs start untainted
        m = dict(zip(sub_in, in_t + [TAINT_NONE] * (len(sub_in) - len(in_t))))
        return m
    worst = max(in_t, default=TAINT_NONE)
    return {v: worst for v in sub_in}


def collect_eqns(jaxpr, kv_invars: Iterable[int] = (),
                 const_taints: dict | None = None) -> list[WalkedEqn]:
    """Walk a (Closed)Jaxpr recursively, returning every equation.

    kv_invars: indices of the top-level invars that are raw KV sources
    (pool/near/far K,V buffers).  Taint propagates through every nesting
    level; see the module docstring for the lattice.
    """
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    out: list[WalkedEqn] = []
    kv = set(kv_invars)
    taints: dict = dict(const_taints or {})
    for i, v in enumerate(jaxpr.invars):
        taints[v] = TAINT_RAW if i in kv else taints.get(v, TAINT_NONE)

    def run(jx: jex_core.Jaxpr, taints: dict, path: tuple[str, ...],
            in_pallas: bool):
        def taint_of(v):
            if isinstance(v, jex_core.Literal):
                return TAINT_NONE
            return taints.get(v, TAINT_NONE)

        dot_of_var: dict = {}      # dot outvar -> its WalkedEqn record
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "convert_element_type" and \
                    str(eqn.params.get("new_dtype")) in ("float32",
                                                         "float64"):
                for v in eqn.invars:
                    rec = dot_of_var.get(id(v))
                    if rec is not None:
                        rec.cast_f32 = True
            in_t = [taint_of(v) for v in eqn.invars]
            out.append(WalkedEqn(
                prim=name,
                out_avals=[_aval(v) for v in eqn.outvars],
                in_avals=[_aval(v) for v in eqn.invars],
                in_taints=in_t,
                params=eqn.params,
                path=path,
                in_pallas=in_pallas,
                source=_source_of(eqn)))
            if name == "dot_general":
                for v in eqn.outvars:
                    dot_of_var[id(v)] = out[-1]
            elif name in _TRANSPARENT_PRIMS:
                # a dot's "explicit f32 cast" may sit behind a transpose /
                # reshape the einsum inserted — carry the dot record along
                recs = [dot_of_var[id(v)] for v in eqn.invars
                        if not isinstance(v, jex_core.Literal)
                        and id(v) in dot_of_var]
                if recs:
                    for v in eqn.outvars:
                        dot_of_var[id(v)] = recs[0]
            subs = list(_sub_jaxprs_generic(eqn.params))
            sub_out_taints: list[list[int]] = []
            for _, sub in subs:
                sub_j = _as_jaxpr(sub)
                sub_taints = _call_taint_map(eqn, sub_j, taint_of)
                run(sub_j, sub_taints, path + (name,),
                    in_pallas or name == "pallas_call")
                sub_out_taints.append(
                    [TAINT_NONE if isinstance(v, jex_core.Literal)
                     else sub_taints.get(v, TAINT_NONE)
                     for v in sub_j.outvars])
            # output taint: transparent prims keep RAW alive; call-like
            # prims read it back from their sub-jaxpr's outvars (1:1 for
            # jit/scan/closed_call/custom_*; cond takes the max across
            # branches; the while body's carry IS the eqn output); other
            # arithmetic degrades the max input taint to DERIVED
            exact = [ts for ts in sub_out_taints
                     if len(ts) == len(eqn.outvars)]
            worst = max(in_t, default=TAINT_NONE)
            if name in _TRANSPARENT_PRIMS:
                per_out = [worst] * len(eqn.outvars)
            elif exact:
                per_out = [max(ts[i] for ts in exact)
                           for i in range(len(eqn.outvars))]
            elif worst == TAINT_NONE:
                per_out = [TAINT_NONE] * len(eqn.outvars)
            else:
                per_out = [TAINT_DERIVED] * len(eqn.outvars)
            for v, o in zip(eqn.outvars, per_out):
                taints[v] = o

    run(jaxpr, taints, (), False)
    return out


def intermediate_shapes(jaxpr) -> set[tuple]:
    """Every output shape of every equation, at every nesting depth — the
    drop-in replacement for the old ``_shapes_in`` test helper."""
    shapes: set[tuple] = set()
    for we in collect_eqns(jaxpr):
        for a in we.out_avals:
            if a is not None and hasattr(a, "shape"):
                shapes.add(tuple(a.shape))
    return shapes


def dots(walked: list[WalkedEqn]) -> list[WalkedEqn]:
    """The dot/convolution equations of a walked program."""
    return [we for we in walked
            if we.prim in ("dot_general", "conv_general_dilated")]


def kv_invar_indices(example_args, is_kv_path) -> list[int]:
    """Flatten example args and return the flat indices whose tree path
    satisfies ``is_kv_path`` (a predicate on the jax keypath string) —
    exactly the invar order ``jax.make_jaxpr`` produces."""
    leaves = jax.tree_util.tree_leaves_with_path(example_args)
    idx = []
    for i, (path, _) in enumerate(leaves):
        if is_kv_path(jax.tree_util.keystr(path)):
            idx.append(i)
    return idx


# -- HLO ---------------------------------------------------------------------

def lower_hlo_text(fn, *args, **kwargs) -> str:
    """Compile ``fn(*args)`` and return the optimized HLO module text."""
    return jax.jit(fn, **kwargs).lower(*args).compile().as_text()


def hlo_ops_present(hlo_text: str, ops: Iterable[str]) -> list[str]:
    """Which of ``ops`` (HLO op names, e.g. "all-reduce") appear as
    instructions in the module text.  Matches on " opname(" after the "="
    to avoid false hits in metadata strings."""
    present = []
    for op in ops:
        needle = f" {op}("
        if any(needle in line and "=" in line.split(needle)[0]
               for line in hlo_text.splitlines()):
            present.append(op)
    return present


COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                  "collective-permute", "reduce-scatter")

# jaxpr-level collective primitives -> the HLO op kind each lowers to.
# Unlike optimized HLO (where axis names are erased into replica groups),
# jaxpr collectives still carry their mesh axis names in eqn params — the
# layer where "a psum over the declared 'model' axis" is checkable at all.
COLLECTIVE_PRIMS = {
    "psum": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "pshuffle": "collective-permute",
    "psum_scatter": "reduce-scatter",
}


def collective_axes(we: WalkedEqn) -> tuple[str, ...]:
    """The mesh axis names a jaxpr collective equation reduces/gathers
    over.  psum-family primitives store them under ``axes``; the
    gather/permute family under ``axis_name``; either may be a single name
    or a tuple."""
    axes = we.params.get("axes", we.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def jaxpr_collectives(walked: list[WalkedEqn]) \
        -> list[tuple[WalkedEqn, tuple[str, ...]]]:
    """Every collective equation of a walked program (at any nesting depth
    — ``collect_eqns`` recurses through ``shard_map`` like any other
    call-like primitive) with its axis names."""
    return [(we, collective_axes(we)) for we in walked
            if we.prim in COLLECTIVE_PRIMS]
