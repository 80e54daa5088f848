"""Pallas backends: ``"pallas-tpu"`` and ``"pallas-gpu"``.

Both lower through the same ``pl.pallas_call`` kernel generator
(``lowering_pallas``); on a real accelerator the call lowers to Mosaic (TPU)
or Triton (GPU), while on the CPU the kernels run in interpret mode for
validation (:func:`~repro.core.backend.compile.pallas_interpret`).
What distinguishes the two backends is the *schedule policy*: each resolves
feasibility, defaults and heuristics against its own hardware descriptor
(TPU lane/sublane/VMEM rules vs GPU warp/shared-memory rules), so the same
``StencilProgram`` tunes correctly for a v5e or a P100-class part.
"""

from __future__ import annotations

import jax

from ..hardware import Hardware
from ..stencil.domain import DomainSpec
from ..stencil.ir import Stencil
from ..stencil.schedule import Schedule, k_block
from .base import Backend, Runner, register_backend
from .batching import BatchSpec, pad_wrapped, parse_batch, scan_chunked
from .lowering_pallas import compile_pallas


class PallasTPUBackend(Backend):
    name = "pallas-tpu"
    default_hardware = "tpu-v5e"
    #: vertical-solver temporaries live in pltpu.VMEM scratch (never HBM);
    #: the GPU backend opts out — the TPU memory-space spec has no Triton
    #: equivalent — and keeps temporaries as extra outputs instead
    scratch_temps = True
    #: this backend can place the member axis (and chunk loops) on its grid
    member_grid = True
    native_kernels = True

    def compile_stencil(self, stencil: Stencil, dom: DomainSpec, *,
                        schedule: Schedule | None = None,
                        hardware: Hardware | str | None = None,
                        dtype=None,
                        n_members: int | None = None,
                        batch: "str | BatchSpec" = "grid") -> Runner:
        hw = self.resolve_hw(hardware)
        if schedule is None:
            schedule = self.default_schedule(stencil, dom, hw)
        kwargs = {} if dtype is None else {"dtype": dtype}

        def lower(members=None, chunk=0):
            return compile_pallas(stencil, dom, schedule=schedule,
                                  scratch_temps=self.scratch_temps,
                                  n_members=members, member_chunk=chunk,
                                  vmem_limit=hw.vmem_limit_bytes, **kwargs)

        if not n_members:
            return lower()
        spec = parse_batch(batch)
        if spec.chunk:
            C = spec.chunk_for(n_members)
            padded = spec.padded_members(n_members)
            if spec.loop == "grid":
                # hybrid: chunk loop on the outermost sequential grid axis,
                # C-member blocks inside each kernel
                fn = lower(members=padded, chunk=C)
                return fn if padded == n_members else \
                    pad_wrapped(fn, n_members, padded)
            if C >= n_members:
                spec = BatchSpec(mode=spec.mode)  # one chunk: plain mode
            else:
                # loop="scan": program-of-chunks over the chunk-mode lowering
                chunk_fn = (jax.vmap(lower(), in_axes=(0, None))
                            if spec.mode == "vmap" else lower(members=C))
                return scan_chunked(chunk_fn, n_members, C)
        if spec.mode == "vmap":
            # A/B baseline against the member grid axis: the single-member
            # kernel under jax.vmap (pallas_call's batching rule prepends
            # its own grid dimension)
            return jax.vmap(lower(), in_axes=(0, None))
        return lower(members=n_members)

    def k_block(self, stencil: Stencil, dom: DomainSpec,
                schedule: Schedule | None = None,
                hardware: Hardware | str | None = None) -> int:
        if schedule is None:
            schedule = self.default_schedule(stencil, dom, hardware)
        return k_block(stencil, schedule, dom.nk, scratch=self.scratch_temps)


class PallasGPUBackend(PallasTPUBackend):
    """GPU variant: same kernel generator, GPU schedule rules + defaults.

    The K-slab grid maps naturally to a thread-block z-dimension and the
    in-kernel ``fori_loop`` of vertical solvers to a per-thread sequential
    loop, so the lowering is shared; block_i/block_j from the GPU-feasible
    schedules feed the cost model and (on real GPUs) the Triton tile picker.
    """

    name = "pallas-gpu"
    default_hardware = "p100"
    scratch_temps = False


register_backend(PallasTPUBackend())
register_backend(PallasGPUBackend())
