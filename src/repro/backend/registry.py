"""Backend registry: names -> Backend classes, the setter of the zoo's
matmul route, and the tuned capability-dispatch layer.

``get("desim", unit=..., granularity="panel")`` is the one lookup every
front door (serving, launch, benchmarks, examples, tests) goes through;
registering a new engine (multi-core DES, sharded execution, ...) is a
``@register("name")`` decoration away and every front door picks it up.

``get_tuned`` is the capability-aware variant: it resolves the best
autotuned kernel configuration for (current platform × shape class)
from the :mod:`repro.tune` cache and folds it into the constructor
kwargs.  Precedence: **explicit argument > tuned cache > untuned
default** — passing any kwarg explicitly always wins, and a
missing/invalid cache silently degrades to the untuned defaults.  The
tuned layer prices simulated schedules only: the model zoo's matmul
route is ``core.fusion.default_route()``, which reads no tuning cache.
"""

from __future__ import annotations

from typing import Callable, Optional, Type

from repro.backend.base import Backend
from repro.core import fusion

_REGISTRY: "dict[str, Type[Backend]]" = {}

#: spelling compatibility: old benchmark/engine names -> registry names.
ALIASES = {"analytic": "analytical", "xla": "jax"}


def register(name: str, *,
             override: bool = False) -> Callable[[Type[Backend]], Type[Backend]]:
    """Register a Backend class under ``name``.

    Re-registering the *same* class is idempotent (module re-import
    safety); registering a different class under a taken name raises
    unless ``override=True`` — silent replacement has bitten every
    plugin registry ever.
    """
    def deco(cls: Type[Backend]) -> Type[Backend]:
        existing = _REGISTRY.get(name)
        if existing is not None and existing is not cls and not override:
            raise ValueError(
                f"backend name {name!r} already registered to "
                f"{existing.__name__}; pass register({name!r}, "
                f"override=True) to replace it")
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def resolve(name: str) -> str:
    canon = ALIASES.get(name, name)
    if canon not in _REGISTRY:
        raise KeyError(
            f"unknown backend {name!r}; registered: {available()} "
            f"(aliases: {dict(ALIASES)})")
    return canon


def get(name: str, **kwargs) -> Backend:
    """Instantiate a registered backend by name (aliases accepted)."""
    return _REGISTRY[resolve(name)](**kwargs)


def available() -> "tuple[str, ...]":
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# The model zoo's matmul route lives in ``core.fusion``; this is its one
# setter, so the route speaks registry vocabulary.
# ---------------------------------------------------------------------------

def set_default_matmul_backend(name: str) -> str:
    """Route the model zoo's ``linear``/``cute_matmul`` calls through a
    different executing backend.  Returns the previous route, a name
    this function accepts back."""
    canon = resolve(name)
    cls = _REGISTRY[canon]
    route = getattr(cls, "matmul_string", None)
    if route is None or not cls.executes or cls.models_time:
        raise ValueError(
            f"backend {canon!r} is not an eager matmul route for the "
            "model zoo; use 'jax' or 'pallas' (modelling backends price "
            "schedules, they don't serve projections)")
    prev, fusion._default_route = fusion._default_route, route
    return prev


def matmul_backend_string() -> str:
    """The ``cute_matmul(backend=...)`` string the zoo's calls take."""
    return fusion.default_route()


# ---------------------------------------------------------------------------
# Tuned capability dispatch (the runtime consumer of ``repro.tune``).
# ---------------------------------------------------------------------------

_DISPATCH_PLATFORM = "shuttle"       # the repo's canonical platform
_TUNED_DISPATCH = True


def set_dispatch_platform(platform) -> str:
    """Pin the platform the tuned dispatch resolves against (a name from
    ``repro.core.hardware.PLATFORMS`` or a ``CpuPlatform``).  Returns
    the previous name."""
    global _DISPATCH_PLATFORM
    prev = _DISPATCH_PLATFORM
    _DISPATCH_PLATFORM = _platform_name(platform)
    return prev


def dispatch_platform() -> str:
    return _DISPATCH_PLATFORM


def set_tuned_dispatch(enabled: bool) -> bool:
    """Process-wide kill switch for the tuned cache (explicit arguments
    and untuned defaults are unaffected).  Returns the previous state."""
    global _TUNED_DISPATCH
    prev, _TUNED_DISPATCH = _TUNED_DISPATCH, bool(enabled)
    return prev


def tuned_dispatch_enabled() -> bool:
    return _TUNED_DISPATCH


def _platform_name(platform) -> str:
    from repro.core.hardware import PLATFORMS
    name = getattr(platform, "name", platform)
    if name is None:
        return _DISPATCH_PLATFORM
    if name not in PLATFORMS:
        raise KeyError(f"unknown platform {name!r}; known: "
                       f"{sorted(PLATFORMS)}")
    return name


def tuned_config(*, shape=None, sched=None, bucket: Optional[str] = None,
                 platform=None):
    """The cached :class:`~repro.tune.space.TunedConfig` for (platform ×
    shape class), or ``None`` when untuned (no cache entry, dispatch
    disabled, or no shape class derivable).

    The shape class comes from ``bucket`` (a literal cache key),
    ``sched`` (a serving ``BatchSchedule``), or ``shape`` (an ``(m, n,
    k)`` tuple or a ``MatMulTask``), in that precedence order.
    """
    if not _TUNED_DISPATCH:
        return None
    from repro import tune
    if bucket is None:
        if sched is not None:
            bucket = tune.schedule_bucket(sched)
        elif shape is not None:
            if hasattr(shape, "m"):
                shape = (shape.m, shape.n, shape.k)
            bucket = f"gemm|{tune.shape_bucket(*shape)}"
        else:
            return None
    return tune.lookup(_platform_name(platform), bucket)


def get_tuned(name: str, *, shape=None, sched=None,
              bucket: Optional[str] = None, **explicit) -> Backend:
    """Instantiate ``name`` with the best tuned configuration for the
    current platform and the given shape class.

    Explicit kwargs win over tuned ones; tuned ones win over the
    backend's untuned defaults; with no usable cache entry this is
    exactly ``get(name, **explicit)``.  Tuned kwargs a backend cannot
    accept (``k_stream`` on single-unit engines) are dropped, and a
    tuned ``overlap`` choice is applied by the serving engine (it is a
    schedule attribute, not a constructor kwarg).
    """
    cls = _REGISTRY[resolve(name)]
    cfg = tuned_config(shape=shape, sched=sched, bucket=bucket,
                       platform=explicit.get("platform"))
    kw: dict = {}
    if cfg is not None:
        from repro.core.config import CASE_STUDY
        base_unit = explicit.get("unit", CASE_STUDY)
        kw = cfg.backend_kwargs(base_unit)
        if not cls.supports_units:
            kw.pop("k_stream", None)
    kw.update(explicit)
    return cls(**kw)
