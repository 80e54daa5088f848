"""Orchestration — whole-program compilation (paper §V-B).

``orchestrate`` turns a StencilProgram (or any pytree-functional step) into a
single jitted callable: one XLA program for the full dynamical core, no
Python interpreter on the hot path, cross-stencil optimization enabled.

The paper's productivity escape hatches map onto JAX natively:
 * constant propagation / loop unrolling  → Python-level closure over config
   (``bind_constants``) — values are baked into the jaxpr exactly like the
   paper's preprocessor propagates dictionary accesses;
 * closure resolution                     → functional params pytrees;
 * automatic callbacks (print/plot/debug) → ``jax.experimental.io_callback``
   hooks registered via ``Monitor`` (the ``__pystate`` ordering token is
   jax's own effect ordering).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import io_callback


@dataclasses.dataclass
class Monitor:
    """Python-side callback registry usable inside orchestrated code."""

    hooks: dict[str, Callable] = dataclasses.field(default_factory=dict)
    enabled: bool = True

    def register(self, name: str, fn: Callable) -> None:
        self.hooks[name] = fn

    def emit(self, name: str, value) -> None:
        """Call from inside jitted code; value is materialized host-side."""
        if not self.enabled or name not in self.hooks:
            return
        hook = self.hooks[name]

        def _cb(v):
            hook(v)
            return jnp.zeros((), jnp.int32)

        io_callback(_cb, jax.ShapeDtypeStruct((), jnp.int32), value, ordered=True)


def bind_constants(fn: Callable, **consts) -> Callable:
    """Constant propagation: bake config values into the traced program."""
    return functools.partial(fn, **consts)


def orchestrate(program_or_fn, *, backend: str = "jnp", hardware=None,
                donate: bool = True,
                opt_level: int = 0) -> Callable:
    """Compile a StencilProgram (or plain function) into one jitted step.

    ``opt_level`` selects the automatic optimization ladder
    (:mod:`repro.core.passes`) for StencilProgram inputs.  ``donate=True``
    donates the fields dict only on platforms where XLA honors donation
    (TPU/GPU); the sequential CPU path would warn and ignore it, so there
    the flag degrades to a plain ``jit``.
    """
    from .backend import compile_program, jit_program
    from .graph import StencilProgram

    if isinstance(program_or_fn, StencilProgram):
        fn = compile_program(program_or_fn, backend, hardware=hardware,
                             opt_level=opt_level)
    else:
        fn = program_or_fn
    return jit_program(fn, backend, donate=donate)
