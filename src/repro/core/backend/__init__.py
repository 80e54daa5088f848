# The hardware-parameterized compilation pipeline: Backend protocol +
# registry, the jnp / pallas-tpu / pallas-gpu lowerings behind it, the
# compile_program entry point, and the persistent tuning cache.  This is the
# only package allowed to touch a lowering module directly.
from ..hardware import (  # noqa: F401
    Hardware,
    P100,
    TPU_V4,
    TPU_V5E,
    V100,
    available_hardware,
    get_hardware,
    register_hardware,
    resolve_hardware,
)
from .base import (  # noqa: F401
    Backend,
    available_backends,
    get_backend,
    register_backend,
)
from .batching import (  # noqa: F401
    AUTO,
    BatchSpec,
    pad_members,
    parse_batch,
    scan_chunked,
)
from .cache import (  # noqa: F401
    CacheStats,
    TuningCache,
    default_cache,
    enable_compile_cache,
    make_key,
    set_default_cache,
    stencil_fingerprint,
)
from .compile import (  # noqa: F401
    clear_compile_cache,
    compile_cache_stats,
    compile_program,
    compile_stencil,
    donation_supported,
    jit_program,
    register_cache_clear,
)

# importing the modules registers the built-in backends
from . import jnp_backend as _jnp_backend  # noqa: F401,E402
from . import pallas as _pallas  # noqa: F401,E402
