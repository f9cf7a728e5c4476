"""Kernel registry: ``(family, op, impl)`` entries resolved into checked sets.

Replaces the stringly-typed ``impl: str`` if/else dispatch that used to
live inline in ``kernels/ops.py``. Implementations *register* themselves
under a ``(family, op, impl)`` triple (``ref`` and ``pallas`` are
ordinary registrations in ``ops.py``, not special cases); callers
resolve entries through :func:`lookup`, whose error names the registered
alternatives instead of silently falling through a branch.

The **sketch family** is the third registry coordinate (DESIGN.md §13):
a :class:`SketchFamily` names the config class, the ops a complete
implementation must provide, the register layouts the family's
semantics tolerate, and the query kinds its estimators can answer.
Families register through :func:`register_family` (the built-ins —
``hll`` and ``ads`` — live in ``repro.core.families``); the engine/
serve/plan layers above resolve everything family-specific through this
module, never by importing ``repro.core`` symbols directly (the
layering gate in ``tools/check_layering.py`` enforces exactly that).

Engines resolve a whole :class:`KernelSet` once at open/load time via
:func:`resolve`: a missing op fails *up front* with the registered impls
listed, and known capability gaps are recorded explicitly — e.g. the
fused estimate kernel only implements the Flajolet s/z combination, so a
``beta``-estimator config gets ``estimate_fallback`` set (and
:meth:`KernelSet.estimate_rows` routes through the jnp reference) rather
than silently branching per call inside the engine.

Pallas interpret mode (off-TPU execution of the kernel bodies) is
resolved per call via :func:`interpret_mode`, never at import time: a
test or launcher that forces a platform after this module is imported
still gets the right mode (the old module-level ``_INTERPRET`` constant
froze the backend seen at import).
"""
from __future__ import annotations

import inspect
from dataclasses import dataclass

import jax

from repro.kernels import tiles
from repro.kernels.packing import LAYOUTS, row_width, validate_layout

__all__ = ["OPS", "LAYOUTS", "PANEL_LIMITS", "register", "lookup", "impls",
           "resolve", "KernelSet", "interpret_mode", "SketchFamily",
           "register_family", "family", "families", "family_of"]

#: op names a complete **hll** kernel implementation provides (the §4 hot
#: paths, including the §10 fused query-estimation ops). Kept as the
#: module-level tuple for backward compatibility; each family carries its
#: own op tuple (``SketchFamily.ops``).
OPS = ("accumulate", "propagate", "estimate", "ertl_stats",
       "union_estimate", "intersection_stats")

#: ops whose plans hand every impl a padding mask (bucketed inputs); an
#: impl that cannot accept one would silently merge padding, so resolve()
#: rejects it up front.
MASKED_OPS = ("accumulate", "propagate", "union_estimate")

#: impls whose kernels pin the whole register panel in VMEM, and the
#: largest panel (bytes) they compile for on the TPU.
PANEL_LIMITS = {"pallas": tiles.PANEL_VMEM_BYTES}

_REGISTRY: dict[tuple[str, str, str], object] = {}
_FAMILIES: dict[str, "SketchFamily"] = {}
_BOOTSTRAPPED = False


class SketchFamily:
    """One sketch family: config + register semantics + query surface.

    The protocol the engine stack programs against (DESIGN.md §13).
    Subclasses (``repro.core.families``) bind the family-specific math —
    config (de)serialization, empty-table construction, estimator
    fallbacks, pair/triangle estimation — so ``engine/``, ``serve/`` and
    the plan builders never import ``repro.core`` symbols directly.

    Class attributes every family defines:
      name: registry coordinate ("hll" | "ads" | ...).
      config_cls: the frozen config dataclass (``p``/``seed``/
        ``estimator`` fields at minimum).
      ops: op names a complete kernel implementation must register under
        this family for :func:`resolve` to accept it.
      layouts: register-panel layouts the family's semantics tolerate
        (ADS is byte-only: 4-bit saturation corrupts HIP inverse
        probabilities).
      query_kinds: engine/server query kinds the family's estimators
        answer; anything else raises ``engine.UnsupportedQuery``.
      default_estimator: estimator assumed when resolving without a cfg.
      default_iters: iteration default for iterative pair estimators
        (``None`` when the family has none).
    """

    name: str = ""
    config_cls: type = None
    ops: tuple = ()
    layouts: tuple = ("byte",)
    query_kinds: tuple = ()
    default_estimator: str = "flajolet"
    default_iters: int | None = None

    def default_config(self):
        """A default-constructed config for this family."""
        return self.config_cls()

    def config_dict(self, cfg) -> dict:
        """JSON-ready config fields for checkpoint manifests."""
        return {"p": cfg.p, "seed": cfg.seed, "estimator": cfg.estimator}

    def config_from_dict(self, d: dict):
        """Rebuild a config from :meth:`config_dict` output."""
        return self.config_cls(**d)

    def empty_table(self, n: int, cfg, layout: str = "byte"):
        """Zeroed register table for ``n`` sketches under ``layout``."""
        raise NotImplementedError

    def resolve_fallback(self, estimator: str) -> str | None:
        """Reason row estimation cannot use the fused kernel, or None."""
        return None

    def fallback_estimate(self, regs, cfg, layout: str):
        """Row estimates through the family's reference path (fallbacks)."""
        raise NotImplementedError(
            f"family {self.name!r} has no estimate fallback path")

    def estimate_from_pair_stats(self, stats, sz, cfg, method: str,
                                 iters: int):
        """Pairwise intersection estimates from fused pair statistics."""
        raise NotImplementedError(
            f"family {self.name!r} does not answer intersection queries")

    def triangle_local(self, regs, n: int, cfg, edges, k: int, mode: str,
                       iters: int, layout: str):
        """Local-backend triangle heavy hitters over a register panel."""
        raise NotImplementedError(
            f"family {self.name!r} does not answer triangle queries")

    def hip_histogram(self, curve):
        """Per-hop distance histogram from a cumulative HIP curve."""
        raise NotImplementedError(
            f"family {self.name!r} does not answer distance queries")

    def hip_closeness(self, curve):
        """Closeness centralities from a cumulative HIP curve."""
        raise NotImplementedError(
            f"family {self.name!r} does not answer distance queries")

    def hip_effective_diameter(self, glob, q: float):
        """Effective diameter from the global cumulative HIP curve."""
        raise NotImplementedError(
            f"family {self.name!r} does not answer distance queries")


def register_family(fam: SketchFamily) -> SketchFamily:
    """Register a :class:`SketchFamily` instance under its ``name``.

    Re-registering the same name with a different instance is an error —
    family names are a persistence coordinate (checkpoint manifests).
    """
    existing = _FAMILIES.get(fam.name)
    if existing is not None and type(existing) is not type(fam):
        raise ValueError(f"sketch family {fam.name!r} is already registered")
    _FAMILIES[fam.name] = fam
    return fam


def family(name: str) -> SketchFamily:
    """Resolve a registered family by name; the error lists known names."""
    _ensure_builtins()
    try:
        return _FAMILIES[name]
    except KeyError:
        raise KeyError(
            f"no sketch family registered under {name!r}; known families: "
            f"{families()}") from None


def families() -> list[str]:
    """Sorted names of every registered sketch family."""
    _ensure_builtins()
    return sorted(_FAMILIES)


def family_of(cfg) -> SketchFamily:
    """The family whose config class ``cfg`` is an instance of.

    The reverse mapping engines use to go from a user-supplied config to
    the family coordinate without ever naming a config class themselves.
    """
    _ensure_builtins()
    for fam in _FAMILIES.values():
        if type(cfg) is fam.config_cls:
            return fam
    known = {f.name: f.config_cls.__name__ for f in _FAMILIES.values()}
    raise TypeError(
        f"no sketch family registered for config {type(cfg).__name__}; "
        f"known families: {known}")


def _ensure_builtins() -> None:
    """Import the built-in impls/families once so they self-register."""
    global _BOOTSTRAPPED
    if not _BOOTSTRAPPED:
        from repro.core import families as _families  # noqa: F401
        from repro.kernels import ops  # noqa: F401  (registers ref/pallas)
        _BOOTSTRAPPED = True  # only after success: a failed import must
        # resurface on retry, not be masked by an empty-registry error


def interpret_mode() -> bool:
    """Whether Pallas kernels should run in interpret mode (i.e. off-TPU).

    Evaluated at call time — ``jax.default_backend()`` is consulted when a
    kernel actually runs (trace time), so forcing a platform after import
    (tests, ``JAX_PLATFORMS``, launchers) is honored.
    """
    return jax.default_backend() != "tpu"


def register(op: str, impl: str, family: str = "hll"):
    """Decorator registering ``fn`` under ``(family, op, impl)``.

    Re-registering the same triple with a different function is an error
    — impl names are the unit of selection and must stay unambiguous.
    The same function may register under several families (ADS shares
    the HLL accumulate/propagate/estimate bodies: identical register
    geometry, different estimators on top).
    """
    def deco(fn):
        key = (family, op, impl)
        if key in _REGISTRY and _REGISTRY[key] is not fn:
            raise ValueError(f"kernel {key} is already registered")
        _REGISTRY[key] = fn
        return fn
    return deco


def lookup(op: str, impl: str, family: str = "hll"):
    """Resolve one ``(family, op, impl)`` entry; errors list alternatives."""
    _ensure_builtins()
    try:
        return _REGISTRY[(family, op, impl)]
    except KeyError:
        raise KeyError(
            f"no kernel registered for family={family!r} op={op!r} "
            f"impl={impl!r}; registered impls for {op!r}: "
            f"{impls(op, family)}") from None


def impls(op: str, family: str = "hll") -> list[str]:
    """Sorted impl names registered for ``op`` under ``family``."""
    _ensure_builtins()
    return sorted(i for (f, o, i) in _REGISTRY if o == op and f == family)


@dataclass(frozen=True)
class KernelSet:
    """A capability-checked bundle of kernels for one ``(family, impl)``.

    Resolved once per engine (at open/load) by :func:`resolve`; hashable
    and value-comparable, so it can ride inside plan-cache keys. Methods
    delegate to the ``kernels.ops`` glue (padding, hashing, donation)
    with ``impl``/``family`` fixed.

    Attributes:
      impl: registered implementation name ("ref" | "pallas" | ...).
      estimator: the config estimator this set was resolved for.
      estimate_fallback: ``None`` when the fused estimate kernel serves
        ``estimator``; otherwise the human-readable reason row estimation
        routes through the family's reference path (explicit, not silent).
      layout: register-panel layout this set operates on ("byte" |
        "packed", DESIGN.md §11) — threaded into every op call so a
        packed engine never hands a half-width panel to byte-layout code.
      family: sketch-family registry coordinate ("hll" | "ads", §13).

    Block-size arguments default to ``None``, which resolves through the
    autotune cache (``kernels.autotune``): the per-``(device_kind, p,
    op)`` winner off-TPU falls back to a deterministic table, so tests
    and CI never sweep.
    """

    impl: str
    estimator: str = "flajolet"
    estimate_fallback: str | None = None
    layout: str = "byte"
    family: str = "hll"

    def accumulate(self, regs, rows, keys, cfg, mask=None, edge_block=None):
        """Algorithm 1 INSERT over an edge block (see ``ops.accumulate``)."""
        from repro.kernels import ops
        return ops.accumulate(regs, rows, keys, cfg, mask=mask,
                              impl=self.impl, edge_block=edge_block,
                              layout=self.layout, family=self.family)

    def accumulate_donated(self, regs, rows, keys, mask, *, cfg,
                           edge_block=None):
        """Donating accumulate — the ingestion hot path entry.

        The register panel is donated through the jit boundary (see
        ``ops.accumulate_donated``); the caller's ``regs`` reference is
        consumed.
        """
        from repro.kernels import ops
        return ops.accumulate_donated(regs, rows, keys, mask, cfg=cfg,
                                      impl=self.impl, edge_block=edge_block,
                                      layout=self.layout, family=self.family)

    def propagate(self, regs, src, dst, mask=None, edge_block=None):
        """One Algorithm 2 merge pass (see ``ops.propagate``)."""
        from repro.kernels import ops
        return ops.propagate(regs, src, dst, mask=mask, impl=self.impl,
                             edge_block=edge_block, layout=self.layout,
                             family=self.family)

    def ertl_stats(self, a, b, cfg, pair_block=None):
        """Eq. (19) pair statistics (see ``ops.ertl_stats``)."""
        from repro.kernels import ops
        return ops.ertl_stats(a, b, cfg, impl=self.impl,
                              pair_block=pair_block, layout=self.layout,
                              family=self.family)

    def union_estimate(self, regs, ids, mask, cfg, set_block=None):
        """Fused batched union estimates (see ``ops.union_estimate``).

        Estimator-agnostic: the kernel reduces merged rows to (s, z) and
        the combination honors ``cfg.estimator`` outside — no fallback
        needed for beta configs (DESIGN.md §10).
        """
        from repro.kernels import ops
        return ops.union_estimate(regs, ids, mask, cfg, impl=self.impl,
                                  set_block=set_block, layout=self.layout,
                                  family=self.family)

    def intersection_stats(self, regs, pairs, cfg, pair_block=None):
        """Fused per-pair T̃(xy) statistics (see ``ops.intersection_stats``).

        Returns ``(stats float32[B, 5, q+2], sz float32[B, 3, 2])`` for
        the family's ``estimate_from_pair_stats`` to consume.
        """
        from repro.kernels import ops
        return ops.intersection_stats(regs, pairs, cfg, impl=self.impl,
                                      pair_block=pair_block,
                                      layout=self.layout, family=self.family)

    def hip_delta(self, prev, cur, row_block=None):
        """Batch-HIP per-row increments between hop panels (ADS family).

        Returns float32[N] of summed inverse change probabilities
        (``core.ads.hip_delta`` semantics; see ``ops.hip_delta``).
        """
        from repro.kernels import ops
        return ops.hip_delta(prev, cur, impl=self.impl, row_block=row_block,
                             layout=self.layout, family=self.family)

    def estimate_rows(self, regs, cfg):
        """Per-row cardinality estimates honoring ``cfg.estimator``.

        Routes through the fused s/z kernel when it supports the
        estimator; otherwise takes the fallback recorded at resolve time
        (``estimate_fallback`` says why) through the family's reference
        path. The decision was made once, at :func:`resolve` — this
        method never silently picks a path the engine did not sign up
        for.
        """
        from repro.kernels import ops
        if self.estimate_fallback is not None:
            return family(self.family).fallback_estimate(
                regs, cfg, self.layout)
        return ops.estimate(regs, cfg, impl=self.impl, layout=self.layout,
                            family=self.family)


def resolve(impl: str, cfg=None, layout: str = "byte",
            family: str | None = None,
            rows: int | None = None) -> KernelSet:
    """Capability-check ``impl`` against a family's ops; bundle a KernelSet.

    Raises ``ValueError`` (naming the registered impls) if ``impl`` does
    not provide every op the family requires — engines call this at
    open/load so an unknown or partial impl fails before any
    accumulation work. ``family`` defaults to the family of ``cfg``
    (``"hll"`` when neither is given); ``cfg`` determines estimator
    capability via the family's ``resolve_fallback``. ``layout`` selects
    the register-panel representation ("byte" | "packed"); it must be
    one the family's semantics tolerate (ADS is byte-only, DESIGN.md
    §13), and every registered op must accept a ``layout`` keyword so a
    packed engine cannot reach an impl that would misread half-width
    panels. ``rows`` (with ``cfg``) is the register panel's row count:
    an impl in :data:`PANEL_LIMITS` refuses a panel larger than its
    bound with a ``ValueError`` naming it, before any kernel compiles.
    """
    _ensure_builtins()
    validate_layout(layout)
    if family is None:
        fam = family_of(cfg) if cfg is not None else _FAMILIES["hll"]
    else:
        fam = _FAMILIES.get(family)
        if fam is None:
            raise KeyError(f"no sketch family registered under {family!r}; "
                           f"known families: {families()}")
        if cfg is not None and type(cfg) is not fam.config_cls:
            raise TypeError(
                f"config {type(cfg).__name__} does not belong to sketch "
                f"family {fam.name!r} (expects {fam.config_cls.__name__})")
    if layout not in fam.layouts:
        raise ValueError(
            f"sketch family {fam.name!r} supports layouts {fam.layouts}, "
            f"not {layout!r} (DESIGN.md §13: ADS inverse probabilities "
            f"need full-width registers)")
    missing = [op for op in fam.ops if (fam.name, op, impl) not in _REGISTRY]
    if missing:
        known = sorted({i for (f, _, i) in _REGISTRY if f == fam.name})
        raise ValueError(
            f"impl must be a fully registered kernel implementation; "
            f"{impl!r} lacks {missing} for family {fam.name!r} "
            f"(registered impls: {known})")
    # capability: the shape-bucketed plans (DESIGN.md §3c, §10) hand every
    # impl of a MASKED_OPS op a padding mask — an impl that cannot accept
    # one would silently merge padding edges/lanes, so it fails here.
    # Likewise every op receives the panel layout; an impl without the
    # keyword would treat packed bytes as byte-layout registers.
    for op in fam.ops:
        sig = inspect.signature(_REGISTRY[(fam.name, op, impl)])
        has_var_kw = any(p.kind is inspect.Parameter.VAR_KEYWORD
                         for p in sig.parameters.values())
        if op in MASKED_OPS:
            accepts_mask = ("mask" in sig.parameters
                            or any(p.kind is inspect.Parameter.VAR_POSITIONAL
                                   for p in sig.parameters.values()))
            if not accepts_mask:
                raise ValueError(
                    f"{op} impl {impl!r} does not accept a 'mask' argument; "
                    f"bucketed {op} plans pad their inputs and require "
                    f"masked-out slots (signature: {sig})")
        if "layout" not in sig.parameters and not has_var_kw:
            raise ValueError(
                f"{op} impl {impl!r} does not accept a 'layout' argument; "
                f"engines thread the register-panel layout through every "
                f"op (DESIGN.md §11; signature: {sig})")
    limit = PANEL_LIMITS.get(impl)
    if rows is not None and limit is not None:
        nbytes = rows * row_width(cfg.r, layout)
        if nbytes > limit:
            raise ValueError(
                f"impl {impl!r} pins the register panel in VMEM: {rows} rows "
                f"x {row_width(cfg.r, layout)} bytes = {nbytes} bytes "
                f"exceeds the bound of {limit} bytes "
                f"(registry.PANEL_LIMITS); use impl='ref' or fewer rows")
    estimator = (getattr(cfg, "estimator", fam.default_estimator)
                 if cfg else fam.default_estimator)
    fallback = fam.resolve_fallback(estimator)
    return KernelSet(impl=impl, estimator=estimator,
                     estimate_fallback=fallback, layout=layout,
                     family=fam.name)
