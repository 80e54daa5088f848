"""Host seconds in ``.compile()`` of the lowered step (XLA compile, served
from JAX's persistent cache after a checkout's first run)."""


def read(record):
    return record["host"]["compile_time"]
