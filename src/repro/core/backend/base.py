"""Backend protocol + registry.

A :class:`Backend` owns one lowering of the stencil IR and the
hardware-default choices that go with it.  Backends register by name;
everything above this layer (graph compilation, autotuning, the FV3 dycore,
benchmarks) resolves backends through :func:`get_backend` and never imports
a lowering module directly — the pluggable-backend architecture of Devito
and DaCe that the paper's portability claim rests on.

Adding a backend (``compile_program`` passes every keyword below on each
compile, so the signature must accept them all — wrap the single-member
runner in ``jax.vmap`` when asked for ``n_members`` and you have no grid
to offer):

    class MyBackend(Backend):
        name = "my-target"
        default_hardware = "tpu-v5e"
        def compile_stencil(self, stencil, dom, *, schedule=None,
                            hardware=None, dtype=...,
                            n_members=None, batch="vmap"):
            return <callable fn(fields, params) -> dict>

    register_backend(MyBackend())
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Iterator, Mapping

from ..hardware import Hardware, resolve_hardware
from ..stencil.domain import DomainSpec
from ..stencil.ir import Stencil
from ..stencil.schedule import (
    Schedule,
    default_schedule,
    feasible_schedules,
    heuristic_schedule,
)

Runner = Callable[[Mapping[str, Any], Mapping[str, Any] | None], dict]


class Backend(abc.ABC):
    """One lowering target of the stencil IR."""

    #: registry key, e.g. "jnp" / "pallas-tpu" / "pallas-gpu"
    name: str = ""
    #: name of the hardware descriptor assumed when the caller passes none
    default_hardware: str = "tpu-v5e"
    #: True when the backend can place the ensemble member axis (and the
    #: hybrid chunk loop, ``batch="vmap:C,grid"``) on its own launch
    #: structure; False → ``"grid"`` modes degrade to vmap/scan
    member_grid: bool = False
    #: True when the backend's programs hold custom kernels the chip's own
    #: kernel compiler builds (Pallas → Mosaic/Triton), so jitting them
    #: takes the attached chip's ``Hardware.kernel_step_options``
    native_kernels: bool = False

    def resolve_hw(self, hardware: Hardware | str | None) -> Hardware:
        return resolve_hardware(hardware, default=self.default_hardware)

    @abc.abstractmethod
    def compile_stencil(self, stencil: Stencil, dom: DomainSpec, *,
                        schedule: Schedule | None = None,
                        hardware: Hardware | str | None = None,
                        dtype=None,
                        n_members: int | None = None,
                        batch: str = "vmap") -> Runner:
        """Lower one stencil into ``fn(fields, params) -> dict``.

        ``n_members=M`` compiles an ensemble-batched runner: every field
        carries a leading member axis of extent M.  ``batch`` selects the
        lowering of that axis — a spec string parsed by
        :func:`~repro.core.backend.batching.parse_batch` (or an already-
        parsed :class:`~repro.core.backend.batching.BatchSpec`):
        ``"vmap"`` wraps the single-member runner in :func:`jax.vmap`
        (the jnp backend's only inner strategy: XLA owns the mapping);
        ``"grid"`` asks the backend to place members on its own launch
        structure (the Pallas backends prepend an outermost sequential
        grid axis); chunked hybrids ``"vmap:C"`` / ``"vmap:C,grid"`` /
        ``"grid:C"`` tile the axis into ceil(M/C)-long chunk loops (scan
        or outermost grid) over C-wide inner batches.  Backends without a
        grid notion (``member_grid=False``) treat every "grid" mode as its
        vmap/scan equivalent.
        """

    # -- schedule policy (hardware-parameterized, overridable) ---------------
    def feasible_schedules(self, stencil: Stencil, dom_shape,
                           dtype_bytes: int = 4,
                           hardware: Hardware | str | None = None,
                           ) -> Iterator[Schedule]:
        return feasible_schedules(stencil, dom_shape, dtype_bytes,
                                  hw=self.resolve_hw(hardware))

    def default_schedule(self, stencil: Stencil, dom_shape,
                         hardware: Hardware | str | None = None) -> Schedule:
        return default_schedule(stencil, dom_shape,
                                hw=self.resolve_hw(hardware))

    def heuristic_schedule(self, stencil: Stencil, dom_shape,
                           hardware: Hardware | str | None = None) -> Schedule:
        return heuristic_schedule(stencil, dom_shape,
                                  hw=self.resolve_hw(hardware))

    def k_block(self, stencil: Stencil, dom: DomainSpec,
                schedule: Schedule | None = None,
                hardware: Hardware | str | None = None) -> int:
        """Levels per block in which this backend's kernel of ``stencil``
        walks K (0: whole columns, as a backend without K blocks does)."""
        return 0

    def __repr__(self):
        return f"<backend {self.name!r} (default hw {self.default_hardware})>"


_REGISTRY: dict[str, Backend] = {}
#: historical spellings accepted by ``StencilProgram.compile``
_ALIASES = {"pallas": "pallas-tpu"}


def register_backend(backend: Backend, *, overwrite: bool = False) -> Backend:
    if not backend.name:
        raise ValueError("backend must define a non-empty .name")
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: "str | Backend") -> Backend:
    if isinstance(name, Backend):
        return name
    key = _ALIASES.get(name, name)
    try:
        return _REGISTRY[key]
    except KeyError:
        known = ", ".join(sorted(_REGISTRY))
        raise KeyError(
            f"unknown backend {name!r}; registered: {known}") from None


def available_backends() -> list[str]:
    return sorted(_REGISTRY)
