"""Backend selection: one validated backend name per dispatch path.

This module imports nothing else of the package, so ``repro.core.engine``
can import :class:`BackendPolicy` whichever of ``repro.kernels`` and
``repro.core`` is imported first. Each dispatcher's ``BACKENDS`` tuple is
its entry of :data:`BACKEND_CHOICES`.
"""
import dataclasses

BACKEND_CHOICES = {
    "fitness": ("auto", "kernel", "interpret", "ref", "jnp"),
    "variation": ("auto", "kernel", "interpret", "ref", "ops"),
    "generation": ("auto", "kernel", "interpret", "ref", "phases"),
    "ranking": ("auto", "sweep", "matrix"),
}


@dataclasses.dataclass(frozen=True)
class BackendPolicy:
    """One validated backend name per dispatch path.

    The replacement for the four stringly-typed ``GAConfig.*_backend``
    knobs: ``GAConfig(backends=BackendPolicy(fitness="ref"))``. Every
    field defaults to ``"auto"`` (Pallas kernel on TPU, fused jnp
    elsewhere); unknown names raise ``ValueError`` here, at construction.
    The old kwargs still work as deprecated aliases that populate this
    policy (``GAConfig.__post_init__``).
    """

    fitness: str = "auto"
    variation: str = "auto"
    generation: str = "auto"
    ranking: str = "auto"

    def __post_init__(self):
        for path, choices in BACKEND_CHOICES.items():
            name = getattr(self, path)
            if name not in choices:
                raise ValueError(
                    f"unknown {path} backend {name!r}: expected one of "
                    f"{choices}")


def resolve_backends(policy=None, **overrides) -> BackendPolicy:
    """THE resolver from loose backend names to a validated policy.

    ``policy``: an existing :class:`BackendPolicy` (or None for all-auto).
    ``overrides``: per-path names (``fitness=…``, ``ranking=…``, …); a
    ``None`` override means "keep the policy's choice". Unknown path or
    backend names raise ``ValueError``. Returns a (possibly new) frozen
    ``BackendPolicy``. A backend that cannot compile on this host fails
    when it is dispatched; nothing is swapped for it here.
    """
    base = policy if policy is not None else BackendPolicy()
    bad = set(overrides) - set(BACKEND_CHOICES)
    if bad:
        raise ValueError(f"unknown backend paths {sorted(bad)}: expected "
                         f"a subset of {sorted(BACKEND_CHOICES)}")
    kept = {k: v for k, v in overrides.items() if v is not None}
    return dataclasses.replace(base, **kept) if kept else base
