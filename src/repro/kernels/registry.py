"""Filter-backend registry — the ONE place backend selection happens.

Three PRs of growth left backend choice scattered across three idioms: the
``MATE_FILTER_BACKEND`` env var read inside ``kernels/ops.py``, ``fused=`` /
``use_kernel=`` booleans on the engines, and ``impl=`` strings on the
distributed filter.  This module centralises all of it:

  * ``Backend`` — a frozen, resolved selection.  Engines and wrappers take a
    ``Backend`` (or a name that resolves to one) instead of ad-hoc booleans.
  * ``resolve_backend(backend, platform)`` — the single precedence rule:

        explicit config  >  MATE_FILTER_BACKEND env var  >  platform default

    (platform default: ``fused-gather`` on TPU — the roofline path, demoting
    to ``fused``, counted in stats, when the device superkey store is over
    budget — and ``auto`` everywhere else, where ``auto`` is the size-based
    numpy/XLA split).
  * ``register_backend`` — the extension point; the built-in table covers
    the four §6.3 filter implementations plus ``auto``.

NO other module may read ``MATE_FILTER_BACKEND`` — CI lints for it
(``tools/lint_backend_env.py``) so the env var cannot quietly grow new
readers again.
"""

from __future__ import annotations

import dataclasses
import os

import jax

ENV_VAR = "MATE_FILTER_BACKEND"


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Registry entry describing one filter implementation."""

    name: str
    description: str
    fused: bool = False  # counts-only launch; match matrix never exists
    device: bool = True  # launches device work (False: host numpy oracle)
    gather: bool = False  # DMA-gathers rows from the device superkey store


@dataclasses.dataclass(frozen=True)
class Backend:
    """A RESOLVED backend selection: what the engines actually thread.

    ``source`` records which precedence level won ('config' | 'env' |
    'platform') — bench rows and stats surfaces report it so a run's
    provenance is never ambiguous.
    """

    name: str
    source: str = "config"

    @property
    def spec(self) -> BackendSpec:
        return _REGISTRY[self.name]

    @property
    def fused(self) -> bool:
        return self.spec.fused

    @property
    def device(self) -> bool:
        return self.spec.device

    @property
    def gather(self) -> bool:
        return self.spec.gather

    def __str__(self) -> str:  # noqa: DunderStr — used in bench rows/logs
        return self.name


_REGISTRY: dict[str, BackendSpec] = {}


def register_backend(spec: BackendSpec) -> BackendSpec:
    """Register a filter backend; names are unique and immutable."""
    if spec.name in _REGISTRY:
        raise ValueError(f"backend {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


register_backend(BackendSpec(
    "fused", "fused filter+segment-count Pallas kernel (counts-only readback;"
    " interpret mode off-TPU)", fused=True,
))
register_backend(BackendSpec(
    "fused-gather", "gather-fused Pallas kernel: DMA-gathers candidate rows"
    " from the device superkey store inside the fused counts-only launch"
    " (demotes to 'fused', counted, when the store is over budget;"
    " interpret mode off-TPU)", fused=True, gather=True,
))
register_backend(BackendSpec(
    "pallas", "composed Pallas filter_kernel + XLA segment-sum"
    " (interpret mode off-TPU)",
))
register_backend(BackendSpec(
    "xla", "vectorised XLA subsumption",
))
register_backend(BackendSpec(
    "numpy", "host-side numpy oracle", device=False,
))
register_backend(BackendSpec(
    "auto", "size-based numpy/XLA split (CPU default)",
))


def backend_names() -> tuple[str, ...]:
    """Registered backend names (stable registration order)."""
    return tuple(_REGISTRY)


def platform_default(platform: str | None = None) -> str:
    """Backend name a platform defaults to when nothing is pinned."""
    platform = platform or jax.default_backend()
    return "fused-gather" if platform == "tpu" else "auto"


def resolve_backend(
    backend: Backend | str | None = None,
    platform: str | None = None,
) -> Backend:
    """Resolve a backend selection with the one precedence rule.

    ``backend`` may be an already-resolved ``Backend`` (returned as-is), a
    registered name (source='config'), or None — in which case the
    ``MATE_FILTER_BACKEND`` env var applies (source='env') and, failing
    that, the platform default (source='platform').  Unknown names raise,
    from the config and from the env var alike.
    """
    if isinstance(backend, Backend):
        return backend
    if backend is not None:
        if backend not in _REGISTRY:
            raise ValueError(
                f"unknown filter backend {backend!r}; registered: "
                f"{', '.join(_REGISTRY)}"
            )
        return Backend(backend, source="config")
    env = os.environ.get(ENV_VAR, "").strip().lower()
    if not env:
        return Backend(platform_default(platform), source="platform")
    if env not in _REGISTRY:
        raise ValueError(
            f"unknown filter backend {env!r} in {ENV_VAR}; registered: "
            f"{', '.join(_REGISTRY)}"
        )
    return Backend(env, source="env")
