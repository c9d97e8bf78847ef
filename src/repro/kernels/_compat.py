"""The seed-era per-stencil entry points, built by one parametrized factory.

The ``stencil{3,7,27}`` / ``stencil{3,7,27}_ref`` wrappers are built once by
the ``_make_entry`` / ``_make_ref`` factories below (one parametrized body
instead of three copy-pasted shim packages).  The historical import paths
(``repro.kernels.stencil3`` etc., ``repro.kernels.stencil_engine.compat``,
``repro.kernels._stencil_common``) all re-export from this module.  The one
deliberate behavior change (inherited from the engine migration):
``interpret`` defaults to ``None`` ("interpret only when no compiled Pallas
backend exists"), so the same call site runs compiled on TPU and interpreted
on CPU/GPU/CI.

The wrappers import the engine lazily (inside the traced body) so this
module stays import-cycle-free: ``stencil_engine.compat`` imports the entry
points from here, and that must work whichever module loads first.
"""

from __future__ import annotations

import functools

import jax


# One row per legacy entry point: registry name -> (name of the static
# block-size keyword the seed API used, weights-layout docstring).
_SHIMS = {
    "stencil3": ("block_rows", "Symmetric 3-point stencil along the last "
                               "axis; ``w = (w_edge, w_center)``."),
    "stencil7": ("block_i", "Symmetric 7-point stencil; "
                            "``w = (wc, wk, wj, wi)``."),
    "stencil27": ("block_i", "Symmetric 27-point stencil; ``w`` has shape "
                             "(2, 2, 2)."),
}

# exec template so each wrapper's *signature* carries the historical block
# keyword name (``block_rows`` vs ``block_i``) -- jax.jit resolves
# ``static_argnames`` against the inspected signature, so a generic
# ``**kwargs`` body would not preserve the seed API.
_ENTRY_SRC = '''\
def {name}(a, w, {blk}=None, interpret=None):
    """{doc}"""
    from .stencil_engine.ops import stencil_apply
    return stencil_apply(a, w, "{name}", block_i={blk}, interpret=interpret)
'''


def _make_entry(name: str, blk: str, doc: str):
    """Build the jitted legacy entry point ``name(a, w, <blk>=None,
    interpret=None)`` over the engine's ``stencil_apply``."""
    ns = {"__name__": __name__}
    exec(compile(_ENTRY_SRC.format(name=name, blk=blk, doc=doc),
                 f"<shim {name}>", "exec"), ns)
    fn = ns[name]
    fn.__module__ = __name__
    return functools.partial(jax.jit,
                             static_argnames=(blk, "interpret"))(fn)


def _make_ref(name: str):
    """Build the legacy oracle ``name_ref(a, w)`` over ``stencil_ref``."""
    def ref(a, w):
        from .stencil_engine.ref import stencil_ref
        return stencil_ref(a, w, name)
    ref.__name__ = ref.__qualname__ = f"{name}_ref"
    ref.__doc__ = (f"Pure-jnp oracle for the {name[len('stencil'):]}-point "
                   f"stencil (engine-backed).")
    return ref


stencil3 = _make_entry("stencil3", *_SHIMS["stencil3"])
stencil7 = _make_entry("stencil7", *_SHIMS["stencil7"])
stencil27 = _make_entry("stencil27", *_SHIMS["stencil27"])
stencil3_ref = _make_ref("stencil3")
stencil7_ref = _make_ref("stencil7")
stencil27_ref = _make_ref("stencil27")


# ``repro.kernels._stencil_common`` re-exports: resolved lazily (PEP 562)
# so importing this module never drags in -- or cycles with -- the engine.
_COMMON_REEXPORTS = {
    "pick_block_i": "repro.kernels.stencil_engine.autotune",
    "interior_mask": "repro.kernels.stencil_engine.common",
    "shifted_planes": "repro.kernels.stencil_engine.common",
    "stencil_pallas_call": "repro.kernels.stencil_engine.common",
}


def __getattr__(name: str):
    mod = _COMMON_REEXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib
    return getattr(importlib.import_module(mod), name)
