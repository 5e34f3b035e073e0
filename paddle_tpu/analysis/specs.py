"""Canonical PartitionSpec form — ONE definition of "what jax will
normalize a spec to", shared by the runtime call sites that must agree
on jit-cache identity and by the recompile-hazard lint pass.

The hazard (learned three times over: PR 7 hybrid step outputs, PR 8
trailing-None pool specs, PR 10 EP-mesh ``P()`` collapse): the jit
cache keys on *input shardings*, and two placement-IDENTICAL specs
written differently — ``P('a')`` vs ``P('a', None)``, or
``P(None, None, None, 'mp')`` on a size-1 ``mp`` axis vs ``P()`` —
are DIFFERENT cache keys. (On the installed jax 0.9.0 the second
form is a new entry in jit's dispatch cache and a trip through the
slow dispatch path; it no longer builds a second executable — checked
with ``instrumented_jit``'s compile counter. Older jax compiled
twice, which is how the lesson was learned.)
Whenever a step's output arrays are fed back as the next call's
inputs, the initial ``device_put`` spec and the step's out-spec must
therefore be written in one agreed normal form, or step 2 silently
pays a full recompile.

``canonicalize_spec`` IS that normal form:

* entries naming only size-1 mesh axes are dropped (a size-1 axis
  shards nothing — GSPMD-inferred output specs omit it, which is the
  EP-mesh ``P(None,None,None,'mp')`` -> ``P()`` collapse at tp=1);
* tuple entries lose their size-1 members, a singleton tuple unwraps
  to its bare axis name, an emptied tuple becomes ``None``;
* trailing ``None`` entries are trimmed (the PR 8 pool-spec lesson);
* an all-``None`` spec collapses to ``P()``.

The static-analysis side (``analysis.rules`` RH201/RH202) shares the
trim/collapse logic through ``literal_is_canonical`` so the lint rule
and the runtime code cannot drift apart.
"""
from __future__ import annotations

#: sentinel for spec-literal entries the AST pass cannot evaluate
#: (names, calls, starred expressions) — treated as "shards something",
#: i.e. never trimmable
OPAQUE = object()


def _axis_sizes(mesh):
    """{axis name: size} from a Mesh, a dict, or None (unknown)."""
    if mesh is None:
        return None
    if isinstance(mesh, dict):
        return dict(mesh)
    return dict(mesh.shape)


def _canon_entries(entries, sizes):
    """Core normal-form transform over a list of spec entries. Entries
    are None, axis-name strings, tuples of axis names, or OPAQUE."""
    out = []
    for e in entries:
        if e is None:
            out.append(None)
            continue
        if e is OPAQUE:
            out.append(e)
            continue
        names = e if isinstance(e, tuple) else (e,)
        if sizes is not None:
            names = tuple(n for n in names
                          if n is OPAQUE or sizes.get(n, 0) != 1)
        if not names:
            out.append(None)
        elif len(names) == 1:
            out.append(names[0])
        else:
            out.append(names)
    while out and out[-1] is None:
        out.pop()
    return out


def canonicalize_spec(spec, mesh=None):
    """The canonical `PartitionSpec` for `spec` under `mesh`.

    `mesh` may be a `jax.sharding.Mesh`, a `{axis: size}` dict, or
    None (sizes unknown — size-1 dropping is skipped, trimming still
    applies). Idempotent; placement-equivalent to the input by
    construction (only non-sharding syntax is removed)."""
    from jax.sharding import PartitionSpec as P
    return P(*_canon_entries(list(spec), _axis_sizes(mesh)))


def canonical_sharding(mesh, spec):
    """`NamedSharding(mesh, canonicalize_spec(spec, mesh))` — the
    device_put / out_shardings constructor every feed-outputs-back-in
    call site should use."""
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, canonicalize_spec(spec, mesh))


def literal_is_canonical(entries):
    """Lint-side check over AST-extracted spec-literal entries (None /
    str / tuple-of-str / OPAQUE): is the literal already in normal
    form for EVERY mesh? Mesh-independent only — size-1 axis dropping
    needs runtime sizes, so a spec naming axes is never flagged for
    that (``canonicalize_spec`` at the call site is the fix the rule
    suggests). Returns (ok, why)."""
    ents = list(entries)
    if ents and all(e is None for e in ents):
        return False, ("all-None spec: jax treats it as P() in "
                       "sharding identity but NOT in jit cache keys — "
                       "write P() (or canonicalize_spec)")
    if ents and ents[-1] is None:
        return False, ("trailing-None spec: placement-identical to "
                       "the trimmed form but a DIFFERENT jit cache "
                       "key — trim it (or canonicalize_spec)")
    for e in ents:
        if isinstance(e, tuple) and len(e) == 1:
            return False, ("singleton-tuple entry: P(('a',)) and "
                           "P('a') are different cache keys — unwrap "
                           "it (or canonicalize_spec)")
    return True, ""
