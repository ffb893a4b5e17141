"""Kernel catalog: the kernel-granular tuning plane's registry.

The paper's unit of analysis is the individual short-running kernel, and
the coordinator built in PRs 1–3 is the unit of *management* — this
module makes them meet. Every op module under ``repro/kernels/*/ops.py``
exposes a declarative :class:`KernelDef`; the process-wide
:class:`KernelCatalog` discovers them and builds
:class:`KernelCompilette`\\ s — coordinator-ready generators that know how
to extract their tuning *spec* (the run-time constants: problem shape,
dtype) from live call arguments, how to AOT-compile a variant so the real
XLA compile cost lands in ``gen_spent_s`` (where the async pipeline hides
it), and how to price themselves on a simulated device profile for
deterministic virtual-clock tests.

**Adding a new tunable kernel is ~20 lines** in your ``ops.py``::

    from repro.kernels.catalog import KernelDef
    import jax, jax.numpy as jnp

    def _generate(point, spec, *, interpret=None):
        # close over the point: this is the deGoal specialization analogue
        @jax.jit
        def fn(x):
            return my_kernel(x, point, interpret=interpret)
        return fn

    KERNEL = KernelDef(
        name="mykernel",
        make_space=lambda spec: make_space(spec["N"]),     # reuse yours
        generate=_generate,
        cost_model=my_cost_model,                          # optional
        extract_spec=lambda x, **kw: {"N": x.shape[0],
                                      "dtype": str(x.dtype), **kw},
        abstract_args=lambda spec: (jax.ShapeDtypeStruct(
            (spec["N"],), spec["dtype"]),),
        example_args=lambda spec: (jnp.ones((spec["N"],),
                                            spec["dtype"]),),
    )

Nothing else: ``discover_kernels()`` imports every ``kernels/*/ops.py``
and registers the ``KERNEL`` attribute it finds, the
:class:`~repro.runtime.kernel_plane.KernelTuningPlane` registers built
compilettes with the :class:`~repro.runtime.coordinator.TuningCoordinator`
(own strategy, registry warm-start key, generation-cache entries and
lifecycle bucketing per kernel), and the serve/train CLIs' ``--kernel-
tuning`` / ``--kernel-strategy`` flags pick the kernel up by name.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib
import os
from typing import Any, Callable, Mapping

from repro.core.compilette import Compilette
from repro.core.profiles import DeviceProfile
from repro.core.tuning_space import Point, TuningSpace

__all__ = [
    "KernelDef",
    "KernelCompilette",
    "KernelCatalog",
    "discover_kernels",
    "example_fill",
    "get_catalog",
]


def example_fill(shape: tuple[int, ...], dtype: Any, *,
                 scale: float = 1.0) -> Any:
    """Deterministic non-constant example array for ``example_args``.

    Constant fills make the variant gate vacuous for some kernels —
    e.g. euclidean distances between identical all-ones rows are exactly
    zero, so any multiplicative corruption compares equal to the oracle.
    A short repeating ramp keeps outputs non-degenerate while staying
    cheap, seedless and bit-identical across processes. ``scale`` caps
    the amplitude for kernels that exponentiate (attention softmax).
    """
    import jax.numpy as jnp

    n = 1
    for s in shape:
        n *= int(s)
    vals = ((jnp.arange(n, dtype=jnp.float32) % 13.0) - 6.0) / 6.0 * scale
    return vals.reshape(shape).astype(dtype)


@dataclasses.dataclass(frozen=True)
class KernelDef:
    """Declarative description of one tunable kernel.

    ``generate(point, spec, *, interpret=None)`` must return the concrete
    callable for that tuning point with the spec's run-time constants
    closed over (``interpret=None`` leaves Pallas interpret mode to
    :func:`repro.kernels.pallas_interpret`);
    ``extract_spec(*call_args, **overrides)`` maps live arguments
    (shapes/dtypes) to the spec dict that keys tuners, registry entries
    and generation-cache lines; ``abstract_args(spec)`` /
    ``example_args(spec)`` rebuild AOT avals / concrete evaluation
    arguments from a spec alone.
    """

    name: str
    make_space: Callable[[Mapping[str, Any]], TuningSpace]
    generate: Callable[..., Callable[..., Any]]
    extract_spec: Callable[..., dict[str, Any]]
    cost_model: Callable[
        [Point, Mapping[str, Any], DeviceProfile], float] | None = None
    abstract_args: Callable[[Mapping[str, Any]], tuple] | None = None
    example_args: Callable[[Mapping[str, Any]], tuple] | None = None
    default_point: Point | None = None
    # sha256 prefix of the defining ops.py source, stamped by
    # discover_kernels: persisted bests and cached executables are keyed
    # under it, so editing a kernel's source cold-starts exactly that
    # kernel instead of warm-starting from stale bests
    source_hash: str | None = None
    # correctness reference: ``oracle(*example_args(spec))`` computes the
    # ground-truth output the variant gate compares a freshly generated
    # variant against (the kernel's ``ref.py``); ``tolerance`` supplies
    # per-kernel {"rtol": ..., "atol": ...} bounds for that comparison
    # (kernels accumulating in low precision declare looser ones)
    oracle: Callable[..., Any] | None = None
    tolerance: Mapping[str, float] | None = None


class KernelCompilette(Compilette):
    """A :class:`~repro.core.Compilette` bound to one kernel spec.

    Three generation backends, chosen at build time:

    * **AOT** (default, real backend): the variant is lowered and
      compiled inside ``_generate`` — ``jit(fn).lower(*avals).compile()``
      — so the *actual XLA compile cost* is measured into
      ``generation_time_s`` (and thus ``gen_spent_s``) instead of
      polluting the first evaluation. A compile the backend refuses
      (e.g. a Mosaic VMEM or tiling limit) raises: the tuner records it
      as a generation failure and quarantines the point.
    * **lazy** (``aot=False``): the paper-faithful behaviour before this
      PR — generation returns the un-lowered jit wrapper and the first
      evaluation pays the compile.
    * **virtual** (``virtual=(clock, profile)``): generation returns a
      simulated kernel whose calls advance the injected
      :class:`~repro.core.VirtualClock` by the analytical
      ``cost_model`` estimate — the deterministic backend the tier-1
      kernel-plane tests and ``benchmarks/kernel_plane.py`` run on.
    """

    def __init__(
        self,
        defn: KernelDef,
        spec: Mapping[str, Any],
        *,
        aot: bool = True,
        virtual: "tuple[Any, DeviceProfile] | None" = None,
        gen_cost_s: "float | Callable[..., float] | None" = None,
        cache_token: str | None = None,
    ) -> None:
        self.defn = defn
        self.spec = dict(spec)
        self.aot = bool(aot) and virtual is None
        self.virtual = virtual
        self.aot_compiles = 0
        # correctness gate hooks (read by repro.core.gate.VariantGate):
        # the catalog oracle + tolerances, and an optional scripted
        # verdict ``gate_script(point) -> bool`` — the deterministic
        # pass/fail the virtual backend uses in place of real numerics
        # (installed by tests and the fault-injection replay harness)
        self.oracle = defn.oracle
        self.tolerance = dict(defn.tolerance) if defn.tolerance else None
        self.gate_script: Callable[[Point], bool] | None = None

        cost_model = None
        if defn.cost_model is not None:
            def cost_model(point, sp, profile, _d=defn):
                return _d.cost_model(point, {**self.spec, **sp}, profile)

        super().__init__(
            defn.name,
            defn.make_space(self.spec),
            self._build,
            cost_model=cost_model,
            gen_cost_s=gen_cost_s,
            cache_token=cache_token,
        )
        if defn.source_hash:
            # source identity reaches both persistence layers: the
            # coordinator appends fingerprint_extra to the registry
            # device key, and the generation cache keys on the token —
            # an edited ops.py invalidates this kernel's entries only
            self.fingerprint_extra = f"src-{defn.source_hash}"
            self.cache_token = (
                f"{self.cache_token}+{self.fingerprint_extra}"
                if self.cache_token else self.fingerprint_extra)

    # ------------------------------------------------------------ generate
    def _build(self, point: Point, **sp: Any) -> Callable[..., Any]:
        spec = {**self.spec, **sp}
        if self.virtual is not None:
            clock, profile = self.virtual
            if self.defn.cost_model is None:
                raise ValueError(
                    f"kernel {self.name!r} has no cost model: cannot "
                    "generate virtual variants")
            from repro.core.evaluator import virtual_kernel
            return virtual_kernel(
                clock, self.defn.cost_model(dict(point), spec, profile),
                tag=dict(point))
        fn = self.defn.generate(dict(point), spec)
        if self.aot and self.defn.abstract_args is not None:
            import jax

            jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
            fn = jitted.lower(*self.defn.abstract_args(spec)).compile()
            self.aot_compiles += 1
        return fn

    # ------------------------------------------------------------- helpers
    def has_valid_points(self) -> bool:
        """False when every point is a hole at this spec (untunable shape)."""
        return next(iter(self.space.iter_valid()), None) is not None

    def abstract_call_args(self) -> tuple:
        if self.defn.abstract_args is None:
            raise ValueError(f"kernel {self.name!r} declares no abstract args")
        return self.defn.abstract_args(self.spec)

    def example_call_args(self) -> tuple:
        """Concrete arrays of the spec's shapes (evaluation fallback)."""
        if self.defn.example_args is None:
            raise ValueError(f"kernel {self.name!r} declares no example args")
        return self.defn.example_args(self.spec)


class KernelCatalog:
    """Name → :class:`KernelDef` registry (one per process)."""

    def __init__(self) -> None:
        self._defs: dict[str, KernelDef] = {}

    def register(self, defn: KernelDef) -> KernelDef:
        self._defs[defn.name] = defn
        return defn

    def names(self) -> tuple[str, ...]:
        return tuple(sorted(self._defs))

    def __contains__(self, name: str) -> bool:
        return name in self._defs

    def get(self, name: str) -> KernelDef:
        try:
            return self._defs[name]
        except KeyError:
            raise KeyError(
                f"unknown kernel {name!r}; discovered: "
                f"{', '.join(self.names()) or '(none)'}") from None

    def spec_of(self, name: str, *args: Any, **overrides: Any) -> dict:
        return self.get(name).extract_spec(*args, **overrides)

    def compilette(self, name: str, spec: Mapping[str, Any],
                   **opts: Any) -> KernelCompilette:
        return KernelCompilette(self.get(name), spec, **opts)


_CATALOG = KernelCatalog()
_DISCOVERED = False


def discover_kernels(catalog: KernelCatalog | None = None) -> KernelCatalog:
    """Import every ``repro.kernels.<pkg>.ops`` and register its KERNEL.

    Idempotent; op packages without an ``ops`` module or a ``KERNEL``
    attribute are skipped silently (the kernels layer is optional). The
    scan walks the package path directly (the op directories are PEP-420
    namespace packages, which ``pkgutil.iter_modules`` does not list).
    """
    catalog = catalog if catalog is not None else _CATALOG
    import repro.kernels as pkg

    sources: dict[str, str] = {}
    for root in pkg.__path__:
        for entry in sorted(os.listdir(root)):
            path = os.path.join(root, entry, "ops.py")
            if os.path.isfile(path):
                sources.setdefault(entry, path)
    for name in sorted(sources):
        try:
            mod = importlib.import_module(f"repro.kernels.{name}.ops")
        except ImportError:
            continue
        defn = getattr(mod, "KERNEL", None)
        if isinstance(defn, KernelDef):
            if defn.source_hash is None:
                # stamp in place (the dataclass is frozen, but the ops
                # module's KERNEL object must keep its identity so
                # re-discovery stays idempotent)
                with open(sources[name], "rb") as fh:
                    digest = hashlib.sha256(fh.read()).hexdigest()[:12]
                object.__setattr__(defn, "source_hash", digest)
            catalog.register(defn)
    return catalog


def get_catalog() -> KernelCatalog:
    """The process-wide catalog, discovery run once on first use."""
    global _DISCOVERED
    if not _DISCOVERED:
        discover_kernels(_CATALOG)
        _DISCOVERED = True
    return _CATALOG
