"""Which kernels a jitted step is built from and lowers to: the check
that no XLA fallback ran in a Pallas kernel's place. Copied from
chip_smoke.py (`_mosaic_kernels`, `_pallas_kernels`, `_need`)."""
from __future__ import annotations

import re


def mosaic_kernels(lowered_text):
    """Names of the Mosaic (Pallas TPU) custom calls in a lowered step."""
    if "tpu_custom_call" not in lowered_text:
        return set()
    return set(re.findall(r'kernel_name\s*=\s*"([^"]+)"', lowered_text))


def pallas_kernels(jaxpr):
    """Names of every `pallas_call` in a traced step, nested jaxprs
    included."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    names = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.add(str(eqn.params["name"]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                if isinstance(sub, ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, Jaxpr):
                    names |= pallas_kernels(sub)
    return names


def missing(names, wanted):
    return [w for w in wanted if not any(w in n for n in names)]


def check_step(traced, wanted, rehearse):
    """Kernels of `wanted` that are NOT in the traced step: in its
    jaxpr and, on the chip, in its lowering as Mosaic custom calls.
    Returns (missing, found)."""
    found = pallas_kernels(traced.jaxpr.jaxpr)
    gone = missing(found, wanted)
    if not rehearse:
        lowered = mosaic_kernels(traced.lower().as_text())
        gone = sorted(set(gone) | set(missing(lowered, wanted)))
        found = lowered
    return gone, sorted(found)
