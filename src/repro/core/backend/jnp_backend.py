"""The ``"jnp"`` reference backend — pure-jnp oracle lowering.

Schedules are accepted and ignored: XLA owns all mapping decisions.  This is
the debuggable ground truth every other backend validates against (the
paper's sequential/debug backend role).  The ensemble/member axis lowers via
``jax.vmap`` here regardless of the requested inner ``batch`` mode — there
is no grid to place members on; batching is XLA's decision like everything
else.  Chunked specs (``"vmap:C"``) do apply: the member axis becomes a
``lax.scan`` over ceil(M/C) chunks of a C-wide vmap (an outer="grid" chunk
loop also falls back to this scan — no grid to put it on either).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..hardware import Hardware
from ..stencil.domain import DomainSpec
from ..stencil.ir import Stencil
from ..stencil.schedule import Schedule
from .base import Backend, Runner, register_backend
from .batching import BatchSpec, parse_batch, scan_chunked
from .lowering_jnp import compile_jnp


class JnpBackend(Backend):
    name = "jnp"
    default_hardware = "tpu-v5e"

    def compile_stencil(self, stencil: Stencil, dom: DomainSpec, *,
                        schedule: Schedule | None = None,
                        hardware: Hardware | str | None = None,
                        dtype=None,
                        n_members: int | None = None,
                        batch: "str | BatchSpec" = "vmap") -> Runner:
        fn = compile_jnp(stencil, dom, dtype=dtype or jnp.float32)
        if not n_members:
            return fn
        spec = parse_batch(batch)
        inner = jax.vmap(fn, in_axes=(0, None))
        if spec.chunk:
            C = spec.chunk_for(n_members)
            if C < n_members:
                # vmap adapts to the chunk's leading extent; scan the chunks
                return scan_chunked(inner, n_members, C)
        return inner


register_backend(JnpBackend())
